"""The packed PSRS context's accounting and parts: a layout with no
per-destination slab, the file tier's exact disk bytes against the closed
form, the out-of-core deployment's device budget, and the packed delivery
and the merge's input against plain numpy."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ContextLayout, Pems, PemsConfig
from repro.core.analysis import psrs_context_words, psrs_disk_bytes
from repro.kernels.alltoallv_deliver import deliver_packed
from repro.kernels.alltoallv_deliver.ref import deliver_packed_ref
from repro.kernels.kway_merge import unpack_runs, unpack_runs_ref
from repro.pems_apps import psrs_plan, psrs_sort
from test_psrs_packed import _key_sets

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_psrs_context_holds_no_slab():
    """No field is sized per destination: the context is the sorted data,
    the packed receive field and the result, plus O(v²) words."""
    v, n_v = 16, 1 << 19                       # NPB IS class A, v = 16
    pems, *_ = psrs_plan(v, n_v)
    lo = pems.layout
    for name in lo.names:
        shape = lo.field(name).shape
        assert len(shape) == 1 or np.prod(shape) <= 2 * v * v, (name, shape)
    assert lo.words == psrs_context_words(v, n_v)
    assert lo.words == 5 * n_v + 2 * v * v + 6 * v + 2
    assert lo.mu_bytes <= 5 * n_v * 4 + 64 * 1024


@pytest.mark.parametrize("tier", ["memmap", "file"])
@pytest.mark.parametrize("P", [1, 2])
@pytest.mark.parametrize("driver", ["explicit", "sliced", "async"])
def test_disk_bytes_equal_the_closed_form(tmp_path, tier, P, driver):
    """The ledger bills exactly the bytes psrs_disk_bytes counts: full or
    declared swaps, the collectives, each message's live words each way."""
    keys = _key_sets(n=1024)["npb_is"]
    v = 8
    _, pems = psrs_sort(keys, v=v, k=2, driver=driver, tier=tier, P=P,
                        backing_path=str(tmp_path / "b"), return_pems=True)
    led = pems.merged_shard_ledger()
    assert (led.disk_read_bytes, led.disk_write_bytes) == psrs_disk_bytes(
        v, keys.size // v, driver)


def test_the_class_a_file_job_fits_its_device_budget():
    """The out-of-core configuration's budget holds the round driver's
    blocks in flight, 3·k·μ of the packed context even under the async
    driver; the context with [v, n/v] send and receive slabs would not."""
    with open(os.path.join(_ROOT, "bench", "configs",
                           "psrs.file.v16k1.json")) as f:
        config = json.load(f)
    call = dict(config["call"])
    v = call.pop("v")
    n_v = (1 << config["data"]["total_keys_log2"]) // v
    pems, *_ = psrs_plan(v, n_v, **call)      # would raise past the budget
    assert 3 * pems.layout.mu_bytes <= call["device_cap_bytes"]

    slabs = (ContextLayout().add("data", (n_v,), jnp.int32)
             .add("samp", (v, 2), jnp.int32).add("allsamp", (v, v, 2), jnp.int32)
             .add("gsplit", (v, 2), jnp.int32)
             .add("bsend", (v, n_v), jnp.int32).add("bscnt", (v,), jnp.int32)
             .add("brecv", (v, n_v), jnp.int32).add("brcnt", (v,), jnp.int32)
             .add("result", (2 * n_v,), jnp.int32)
             .add("rcount", (1,), jnp.int32).add("oflow", (1,), jnp.int32))
    with pytest.raises(ValueError, match="device_cap_bytes"):
        Pems(PemsConfig(v=v, k=call["k"], tier="file", driver=call["driver"],
                        device_cap_bytes=call["device_cap_bytes"]), slabs)


def _random_offsets(rng, v, n, kind):
    if kind == "random":
        cuts = np.sort(rng.integers(0, n + 1, size=(v, v - 1)), axis=1)
    elif kind == "one_destination":            # each sender's whole row
        cuts = np.full((v, v - 1), n)
        cuts[:, : v // 2] = 0
    else:                                       # empty messages in between
        cuts = np.sort(rng.choice([0, n // 3, n], size=(v, v - 1)), axis=1)
    zero, full = np.zeros((v, 1), int), np.full((v, 1), n)
    return np.concatenate([zero, cuts, full], axis=1).astype(np.int32)


@pytest.mark.parametrize("kind", ["random", "one_destination", "gaps"])
@pytest.mark.parametrize("path", ["interpret", "reference"])
def test_packed_delivery_matches_numpy(kind, path):
    """Stage, deliver (the kernel in interpret mode, or the dense route)
    and pack equal the plain numpy Alltoallv, overflow cut included."""
    rng = np.random.default_rng(3)
    v, n = 5, 300
    words = rng.integers(0, 2**32, size=(v, n), dtype=np.uint32)
    offsets = _random_offsets(rng, v, n, kind)
    for size in (2 * n, n // 2):               # the second overflows
        got, got_off = deliver_packed(
            jnp.asarray(words), jnp.asarray(offsets), size, fill=0xFFFFFFFF,
            interpret=True if path == "interpret" else None,
            use_kernel=path == "interpret")
        want, want_off = deliver_packed_ref(words, offsets, size,
                                            fill=0xFFFFFFFF)
        np.testing.assert_array_equal(np.asarray(got_off), want_off)
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("kind", ["random", "one_run", "gaps"])
def test_merge_input_matches_numpy(kind):
    """The runs cut from a packed receive field, masked at their counts,
    equal the plain numpy runs, a field cut short included."""
    rng = np.random.default_rng(4)
    v, n = 6, 200
    lengths = {"random": rng.integers(0, n + 1, size=v),
               "one_run": np.eye(v, dtype=int)[2] * n,
               "gaps": np.arange(v) % 2 * n // 3}[kind]
    offs = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int32)
    for size in (int(offs[-1]) + 7, max(1, int(offs[-1]) // 2)):
        packed = rng.integers(-2**31, 2**31 - 1, size=size, dtype=np.int32)
        width = int(np.diff(np.minimum(offs, size)).max()) + 3
        runs, counts = unpack_runs(jnp.asarray(packed), jnp.asarray(offs),
                                   width)
        runs, counts = np.asarray(runs), np.asarray(counts)
        lane = np.arange(width)[None, :]
        got = np.where(lane < counts[:, None], runs, 2**31 - 1)
        want = unpack_runs_ref(packed, offs, fill=2**31 - 1)
        np.testing.assert_array_equal(got[:, : want.shape[1]], want)
        assert (got[:, want.shape[1]:] == 2**31 - 1).all()


def test_the_packed_form_is_asked_for_by_name():
    """1-D fields take the packed form only through ``offsets=``; without
    it they are refused as a malformed [v, ω] exchange, and offsets do not
    mix with counts."""
    v, n_v = 4, 64
    lo = (ContextLayout().add("send", (n_v,), jnp.int32)
          .add("recv", (2 * n_v,), jnp.int32)
          .add("recv1", (n_v,), jnp.int32)
          .add("soff", (v + 1,), jnp.int32).add("roff", (v + 1,), jnp.int32))
    pems = Pems(PemsConfig(v=v, k=2), lo)
    store = pems.init()
    with pytest.raises(ValueError, match="shapes must match"):
        pems.alltoallv(store, "send", "recv", "soff", "roff")
    with pytest.raises(ValueError, match=r"\[v, ω\]"):
        pems.alltoallv(store, "send", "recv1", "soff", "roff")
    with pytest.raises(ValueError, match="offsets= replaces"):
        pems.alltoallv(store, "send", "recv", "soff", "roff",
                       offsets=("soff", "roff"))
    out = pems.alltoallv(store, "send", "recv", offsets=("soff", "roff"))
    assert (np.asarray(out.field("roff")) == 0).all()
