"""Out-of-core backing tier: driver × tier bit-identity (including the
2-process mesh extension of the identity matrix), measured ledger bytes vs
the backing file on disk, collective staging under the device cap, and
checkpoint→restore of a memmap-backed store resuming PSRS mid-stream."""

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.core import ContextLayout, Pems, PemsConfig, TieredStore, WORD
from repro.pems_apps import prefix_sum, psrs_plan, psrs_sort

DRIVERS = ("explicit", "sliced", "async")
TIERS = ("device", "host", "memmap", "file")
DISK_TIERS = ("memmap", "file")


# --------------------------------------------------------------------------- #
# Bit-identity across the driver × tier matrix                                 #
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("driver", DRIVERS)
@pytest.mark.parametrize("tier", TIERS)
def test_psrs_driver_tier_bit_identity(driver, tier):
    rng = np.random.default_rng(11)
    n, v, k = 2048, 8, 2
    data = rng.integers(-2**31, 2**31 - 1, size=n, dtype=np.int32)
    ref = psrs_sort(data, v=v, k=k)          # device/explicit reference
    out, pems = psrs_sort(data, v=v, k=k, driver=driver, tier=tier,
                          return_pems=True)
    np.testing.assert_array_equal(out, ref)
    if tier != "device":
        assert pems.ledger.h2d_bytes > 0 and pems.ledger.d2h_bytes > 0
        assert (pems.ledger.disk_read_bytes > 0) == (tier in DISK_TIERS)
        assert (pems.ledger.syscall_read_bytes > 0) == (tier == "file")


@pytest.mark.parametrize("tier", ("host", "memmap", "file"))
def test_prefix_sum_tier_bit_identity(tier):
    rng = np.random.default_rng(5)
    x = rng.integers(-100, 100, size=1024, dtype=np.int32)
    ref = prefix_sum(x, v=8, k=4)
    for driver in DRIVERS:
        out = prefix_sum(x, v=8, k=4, driver=driver, tier=tier)
        np.testing.assert_array_equal(out, ref)


def test_superstep_tiered_matches_device_with_float_math():
    """Non-trivial float compute through the pipeline: results must be
    bit-identical because every tier traces the same round body."""
    v, k = 8, 2
    ref = {}
    for tier in TIERS:
        lo = ContextLayout().add("x", (32,), jnp.float32)
        pems = Pems(PemsConfig(v=v, k=k, driver="async", tier=tier), lo)
        store = pems.init(lambda rho: {"x": jnp.full(32, rho, jnp.float32)})

        def step(rho, ctx):
            x = ctx.get("x")
            return ctx.set("x", jnp.sin(x) * 2.0 + jnp.sqrt(jnp.abs(x)) + rho)

        store = pems.superstep(store, step)
        ref[tier] = np.asarray(store.field("x"))
    for tier in TIERS[1:]:
        np.testing.assert_array_equal(ref[tier], ref["device"], err_msg=tier)


def test_tiered_collectives_match_device():
    v = 4
    outs = {}
    for tier in TIERS:
        lo = (ContextLayout()
              .add("send", (v, 3), jnp.int32).add("recv", (v, 3), jnp.int32)
              .add("scnt", (v,), jnp.int32).add("rcnt", (v,), jnp.int32)
              .add("x", (5,), jnp.float32).add("o", (5,), jnp.float32)
              .add("g", (v, 5), jnp.float32))
        pems = Pems(PemsConfig(v=v, k=2, tier=tier), lo)
        rng = np.random.default_rng(0)
        st = (pems.init()
              .with_field("send", rng.integers(0, 100, (v, v, 3)).astype(np.int32))
              .with_field("scnt", rng.integers(0, 4, (v, v)).astype(np.int32))
              .with_field("x", rng.standard_normal((v, 5)).astype(np.float32)))
        st = pems.alltoallv(st, "send", "recv", "scnt", "rcnt", fill=-1)
        st = pems.bcast(st, "x", root=1)
        st = pems.gather(st, "x", "g", root=0)
        st = pems.reduce(st, "x", "o", op="add", root=2)
        st = pems.allgather(st, "x", "g")
        outs[tier] = {n: np.asarray(st.field(n))
                      for n in ("recv", "rcnt", "x", "o", "g")}
    for tier in TIERS[1:]:
        for name, arr in outs[tier].items():
            np.testing.assert_array_equal(arr, outs["device"][name],
                                          err_msg=f"{tier}:{name}")


# --------------------------------------------------------------------------- #
# 2-process mesh extension of the identity matrix (subprocess: fake devices)   #
# --------------------------------------------------------------------------- #

_P2_PSRS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, numpy as np
    from repro.pems_apps import psrs_sort

    # Same inputs as test_psrs_driver_tier_bit_identity, so the mesh runs
    # are pinned to the exact bytes the P == 1 identity matrix produces.
    rng = np.random.default_rng(11)
    n, v, k = 2048, 8, 2
    data = rng.integers(-2**31, 2**31 - 1, size=n, dtype=np.int32)
    ref = psrs_sort(data, v=v, k=k)          # P == 1 seed reference
    np.testing.assert_array_equal(ref, np.sort(data))

    from repro.launch.mesh import make_mesh_auto
    mesh = make_mesh_auto((2,), ("vp",))
    for driver in ("explicit", "sliced", "async"):
        for use_kernel in (True, False):
            out = psrs_sort(data, v=v, k=k, driver=driver, P=2, mesh=mesh,
                            use_kernel=use_kernel)
            np.testing.assert_array_equal(out, ref)
    # α-chunked network phase: same bytes regardless of chunking.
    out = psrs_sort(data, v=v, k=k, P=2, mesh=mesh, alpha=2)
    np.testing.assert_array_equal(out, ref)
    print("P2_PSRS_OK")
""")


def test_psrs_driver_mesh_bit_identity_subprocess():
    """driver × use_kernel matrix on a 2-process CPU mesh: the fused
    (src_proc, dst_proc)-tiled delivery route must reproduce the P == 1
    seed reference bit for bit (and so must the dense route)."""
    r = subprocess.run(
        [sys.executable, "-c", _P2_PSRS],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             # Without an explicit platform, jax probes for TPUs via the
             # cloud metadata URL and stalls for minutes off-cloud.
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "P2_PSRS_OK" in r.stdout, r.stderr[-3000:]


# --------------------------------------------------------------------------- #
# Collective staging under the device cap                                      #
# --------------------------------------------------------------------------- #

def _collective_store(tier, alpha=None, cap=None, k=2, v=8, omega=16):
    lo = (ContextLayout()
          .add("send", (v, omega), jnp.int32)
          .add("recv", (v, omega), jnp.int32)
          .add("scnt", (v,), jnp.int32)
          .add("rcnt", (v,), jnp.int32))
    pems = Pems(PemsConfig(v=v, k=k, tier=tier, alpha=alpha,
                           device_cap_bytes=cap), lo)
    rng = np.random.default_rng(0)
    st = (pems.init()
          .with_field("send",
                      rng.integers(0, 100, (v, v, omega)).astype(np.int32))
          .with_field("scnt",
                      rng.integers(0, omega + 1, (v, v)).astype(np.int32)))
    return pems, st


@pytest.mark.parametrize("tier", ("host", "memmap", "file"))
def test_tiered_alltoallv_staging_respects_cap(tier):
    """Tiered Alltoallv staging is chunked by destination (the α knob):
    with a device cap that cannot hold the dense [v, v, ω] matrix, the
    per-chunk staging buffer stays within the cap and the result is still
    bit-identical to the device tier.  The file tier's chunks are read as
    copies (no view into the backing), so its staging counts 2x per chunk —
    still clamped under the cap."""
    v, omega = 8, 16
    col_bytes = v * omega * 4                  # one destination column
    dense_bytes = v * col_bytes                # the [v, v, ω] matrix
    copies = 2 if tier == "file" else 1        # read copy + staging buffer
    pems_d, st_d = _collective_store("device")
    st_d = pems_d.alltoallv(st_d, "send", "recv", "scnt", "rcnt", fill=-1)
    want_r = np.asarray(st_d.field("recv"))
    want_c = np.asarray(st_d.field("rcnt"))

    cap = 5 * col_bytes                        # fits 5 of 8 columns
    assert cap < dense_bytes
    pems, st = _collective_store(tier, cap=cap, k=1)
    st = pems.alltoallv(st, "send", "recv", "scnt", "rcnt", fill=-1)
    np.testing.assert_array_equal(np.asarray(st.field("recv")), want_r)
    np.testing.assert_array_equal(np.asarray(st.field("rcnt")), want_c)
    assert 0 < pems.tier_stats.peak_stage_bytes <= cap

    # The α knob chunks even without a cap; results stay bit-identical.
    for alpha in (1, 3, 8):
        pems, st = _collective_store(tier, alpha=alpha)
        st = pems.alltoallv(st, "send", "recv", "scnt", "rcnt", fill=-1)
        np.testing.assert_array_equal(np.asarray(st.field("recv")), want_r)
        np.testing.assert_array_equal(np.asarray(st.field("rcnt")), want_c)
        assert (pems.tier_stats.peak_stage_bytes
                <= copies * max(alpha, 1) * col_bytes)


def test_tiered_alltoallv_inplace_cap_refused():
    """send == recv must snapshot the whole field; with a cap that cannot
    hold snapshot + chunk the call refuses instead of silently blowing the
    budget (and still works uncapped, bit-identical to the device tier)."""
    v, omega = 8, 16
    lo = ContextLayout().add("send", (v, omega), jnp.int32)
    rng = np.random.default_rng(1)
    M = rng.integers(0, 100, (v, v, omega)).astype(np.int32)

    pems = Pems(PemsConfig(v=v, k=1, tier="host"), lo)
    st = pems.init().with_field("send", M)
    st = pems.alltoallv(st, "send", "send")
    np.testing.assert_array_equal(np.asarray(st.field("send")),
                                  np.swapaxes(M, 0, 1))

    cap = 5 * v * omega * 4                    # < field (v·v·ω) + chunk
    pems = Pems(PemsConfig(v=v, k=1, tier="host", device_cap_bytes=cap), lo)
    st = pems.init().with_field("send", M)
    with pytest.raises(ValueError, match="in-place"):
        pems.alltoallv(st, "send", "send")


def test_tiered_alltoallv_chunked_ledger_bytes():
    """Destination-chunked staging moves exactly the same measured bytes as
    the whole-field staging it replaced: the field once in each direction."""
    v, omega = 8, 16
    pems, st = _collective_store("memmap", alpha=2)
    r0, w0 = pems.ledger.disk_read_bytes, pems.ledger.disk_write_bytes
    st = pems.alltoallv(st, "send", "recv", "scnt", "rcnt", fill=-1)
    field_b = v * v * omega * 4
    counts_b = v * v * 4
    assert pems.ledger.disk_read_bytes - r0 == field_b + counts_b
    assert pems.ledger.disk_write_bytes - w0 == field_b + counts_b


def test_tiered_allgather_stages_one_row():
    """Tiered allgather stages only the gathered [v, ω] row, never the
    [v, v·ω] broadcast."""
    v = 8
    lo = (ContextLayout()
          .add("x", (4,), jnp.int32)
          .add("gath", (v, 4), jnp.int32))
    pems = Pems(PemsConfig(v=v, k=2, tier="host"), lo)
    st = pems.init().with_field(
        "x", (np.arange(v * 4).reshape(v, 4)).astype(np.int32))
    st = pems.allgather(st, "x", "gath")
    want = np.arange(v * 4).reshape(v, 4).astype(np.int32)
    for r in range(v):
        np.testing.assert_array_equal(np.asarray(st.field("gath"))[r], want)
    assert pems.tier_stats.peak_stage_bytes == v * 4 * 4


# --------------------------------------------------------------------------- #
# Measured ledger bytes vs the backing file                                    #
# --------------------------------------------------------------------------- #

def test_ledger_matches_backing_file_touched_ranges(tmp_path):
    """The measured counters equal the exact byte ranges the pipeline
    touches — live allocator words only (§6.6) — and the backing file is
    exactly the vμ the thesis requires (§6.3), written sparsely."""
    v, k, capacity = 8, 2, 64
    lo = (ContextLayout(capacity_words=capacity)
          .add("a", (8,), jnp.int32)
          .add("tmp", (16,), jnp.int32)
          .add("b", (8,), jnp.int32))
    lo.drop("tmp")                      # a live hole: only 16/64 words live
    assert lo.live_words == 16 and lo.words == capacity

    path = str(tmp_path / "ctx.bin")
    pems = Pems(PemsConfig(v=v, k=k, tier="memmap", backing_path=path), lo)
    store = pems.init()
    assert isinstance(store, TieredStore)
    st = os.stat(path)
    assert st.st_size == v * capacity * WORD
    sparse_file = st.st_blocks * 512 < st.st_size  # fs supports sparse files

    store = pems.superstep(
        store, lambda rho, c: c.set("a", c.get("a") + 1).set("b", c.get("b")))
    live_bytes = lo.live_words * WORD
    assert pems.ledger.h2d_bytes == v * live_bytes
    assert pems.ledger.d2h_bytes == v * live_bytes
    assert pems.ledger.disk_read_bytes == v * live_bytes
    assert pems.ledger.disk_write_bytes == v * live_bytes

    if sparse_file:
        # Only live ranges were written: the file's allocated blocks must
        # cover at most the touched pages, not the full vμ.
        touched = os.stat(path).st_blocks * 512
        page = 4096
        worst = v * (-(-capacity * WORD // page) + 1) * page
        assert touched <= worst

    # The sliced driver narrows further: only declared fields move.
    pems2 = Pems(PemsConfig(v=v, k=k, driver="sliced", tier="memmap",
                            backing_path=str(tmp_path / "ctx2.bin")), lo)
    store2 = pems2.init()
    store2 = pems2.superstep(store2, lambda rho, c: c.set("a", c.get("a") + 1),
                             reads=["a"], writes=["a"])
    a_bytes = lo.field_bytes("a")
    assert pems2.ledger.h2d_bytes == v * a_bytes
    assert pems2.ledger.disk_write_bytes == v * a_bytes


def test_modeled_ledger_identical_across_tiers():
    """The thesis' closed-form counters must not depend on the execution
    tier — same swap/message/barrier events everywhere."""
    x = np.arange(512, dtype=np.int32)
    base = None
    for tier in TIERS:
        _, pems = prefix_sum(x, v=8, k=2, tier=tier, return_pems=True)
        modeled = (pems.ledger.swap_in, pems.ledger.swap_out,
                   pems.ledger.message_total, pems.ledger.supersteps,
                   pems.ledger.num_ios)
        if base is None:
            base = modeled
        assert modeled == base, tier


def test_device_cap_enforced():
    lo = ContextLayout().add("x", (1024,), jnp.int32)   # μ = 4096 B
    cap = 4 * lo.mu_bytes                               # fits 4 contexts
    with pytest.raises(ValueError):
        Pems(PemsConfig(v=8, k=1, device_cap_bytes=cap), lo)   # 8μ on device
    with pytest.raises(ValueError):
        # sync tiered: 2·k·μ in-flight = 8μ > cap
        Pems(PemsConfig(v=8, k=4, tier="host", device_cap_bytes=cap), lo)
    with pytest.raises(ValueError):
        # async keeps a third (prefetched) block in flight: 3·2·μ > cap
        Pems(PemsConfig(v=8, k=2, driver="async", tier="host",
                        device_cap_bytes=cap), lo)
    Pems(PemsConfig(v=8, k=2, tier="host", device_cap_bytes=cap), lo)  # 2·2·μ
    Pems(PemsConfig(v=8, k=1, driver="async", tier="host",
                    device_cap_bytes=cap), lo)                         # 3·1·μ


# --------------------------------------------------------------------------- #
# Async overlap instrumentation                                                #
# --------------------------------------------------------------------------- #

def test_async_tier_records_overlap_stats():
    rng = np.random.default_rng(1)
    data = rng.integers(-1000, 1000, size=4096, dtype=np.int32)
    out, pems = psrs_sort(data, v=8, k=2, driver="async", tier="memmap",
                          return_pems=True)
    np.testing.assert_array_equal(out, np.sort(data))
    s = pems.tier_stats
    assert s.rounds > 0 and s.swap_in_s > 0 and s.compute_s > 0
    assert 0.0 <= s.overlap_fraction <= 1.0
    d = s.as_dict()
    assert set(d) >= {"rounds", "swap_in_s", "stall_s", "overlap_fraction"}


# --------------------------------------------------------------------------- #
# Streamed k-way merge stage: prefetch overlap on disk tiers                   #
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("driver", DRIVERS)
@pytest.mark.parametrize("tier", DISK_TIERS)
@pytest.mark.parametrize("P", (1, 2))
def test_streamed_merge_overlap_and_bit_identity(driver, tier, P, tmp_path):
    """PSRS's merge stage runs with stream=True: on disk tiers the next
    round's buckets are read through the block API while the in-flight
    round merges, under every driver (not just "async").  The result must
    stay bit-identical to the device reference, and the streamed-stage
    counters must record the overlap."""
    rng = np.random.default_rng(23)
    n, v, k = 2048, 8, 2
    data = rng.integers(-2**31, 2**31 - 1, size=n, dtype=np.int32)
    ref = psrs_sort(data, v=v, k=k)
    out, pems = psrs_sort(
        data, v=v, k=k, P=P, driver=driver, tier=tier,
        backing_path=str(tmp_path / f"bk_{driver}_{tier}_{P}.bin"),
        return_pems=True)
    np.testing.assert_array_equal(out, ref)
    assert len(pems.shard_stats) == P
    for st in pems.shard_stats:
        # v/(P·k) = 4/P resident rounds in the merge superstep → at least
        # rounds−1 ahead-of-need submissions per shard.
        assert st.merge_prefetch_events >= (v // (P * k)) - 1 > 0
        assert st.merge_stall_s >= 0.0
    merged = pems.merged_shard_stats()
    assert merged.merge_prefetch_events == sum(
        st.merge_prefetch_events for st in pems.shard_stats)
    assert "merge_prefetch_events" in merged.as_dict()


@pytest.mark.parametrize("io_driver", ("buffered", "odirect", "mmap"))
@pytest.mark.parametrize("P", (1, 2))
def test_streamed_merge_file_engines_bit_identical(io_driver, P, tmp_path):
    """tier="file" across the three I/O engines × P ∈ {1, 2}: the streamed
    merge must report overlap events and stay bit-identical."""
    rng = np.random.default_rng(29)
    data = rng.integers(-2**31, 2**31 - 1, size=2048, dtype=np.int32)
    ref = np.sort(data)
    out, pems = psrs_sort(
        data, v=8, k=2, P=P, tier="file", io_driver=io_driver,
        backing_path=str(tmp_path / f"eng_{io_driver}_{P}.bin"),
        return_pems=True)
    np.testing.assert_array_equal(out, ref)
    assert pems.merged_shard_stats().merge_prefetch_events > 0
    assert pems.merged_shard_ledger().syscall_read_bytes > 0


# --------------------------------------------------------------------------- #
# Checkpoint → restore of a memmap-backed store, resuming PSRS                 #
# --------------------------------------------------------------------------- #

def test_checkpoint_restore_memmap_resumes_psrs(tmp_path):
    rng = np.random.default_rng(3)
    n, v, k = 2048, 8, 2
    data = rng.integers(-1000, 1000, size=n,
                        dtype=np.int32).reshape(v, n // v)
    want = np.sort(data.reshape(-1))

    def finish(res, cnt):
        return np.concatenate([res[i, :cnt[i, 0]] for i in range(v)])

    # Run the first five stages (through `partition`), checkpoint the store.
    pems1, load1, steps1, _ = psrs_plan(
        v, n // v, k=k, driver="async", tier="memmap",
        backing_path=str(tmp_path / "a.bin"))
    st1 = load1(data)
    for _, step in steps1[:5]:
        st1 = step(st1)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    mgr.save(5, {"store": st1.backing.arr}, blocking=True)

    # "New process": fresh plan, fresh zeroed backing file, restore in place
    # (never materializing v·mu on device), run the remaining stages.
    pems2, _, steps2, extract2 = psrs_plan(
        v, n // v, k=k, driver="async", tier="memmap",
        backing_path=str(tmp_path / "b.bin"))
    st2 = pems2.init()
    step_got = mgr.restore_latest(like={"store": st2.backing.arr})
    assert step_got is not None and step_got[0] == 5
    assert step_got[1]["store"] is st2.backing.arr   # filled in place
    for _, step in steps2[5:]:
        st2 = step(st2)
    res, cnt, oflow = extract2(st2)
    assert not np.asarray(oflow).any()
    np.testing.assert_array_equal(finish(res, cnt), want)

    # The checkpoint array file must itself be a streamable .npy (memmap
    # flag recorded in the manifest).
    import json
    d = str(tmp_path / "ckpt" / "step_000000000005")
    manifest = json.load(open(os.path.join(d, "manifest.json")))
    assert manifest["arrays"][0]["memmap"] is True
