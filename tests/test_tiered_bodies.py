"""The jitted round bodies of the disk tiers outlive their executor: a
later job of the same static shape neither traces nor lowers them again,
while jobs of another layout or ``k`` get bodies of their own."""

import numpy as np
import pytest

from repro.core import executor
from repro.pems_apps import psrs_sort
from repro.pems_apps.psrs import _stage_fns

_V = 4


def _bodies() -> dict:
    """Every kept round body, by (stage function, layout and k), with the
    number of traces its jit holds."""
    return {(id(fn), key): (body, body._cache_size())
            for fn, per_key in executor._TIERED_BODIES.items()
            for key, body in per_key.items()}


def _job(tmp_path, n_v, k, driver, seed):
    keys = np.random.default_rng(seed).integers(
        -2**31, 2**31 - 1, size=_V * n_v, dtype=np.int32)
    out = psrs_sort(keys, v=_V, k=k, driver=driver, tier="file",
                    backing_path=str(tmp_path / f"b{seed}"))
    np.testing.assert_array_equal(out, np.sort(keys))


@pytest.mark.parametrize("driver", ["explicit", "sliced", "async"])
def test_a_later_job_traces_no_round_body(tmp_path, driver):
    """A second job on a fresh executor reuses every body the first one
    built, each holding the traces it held."""
    n_v = {"explicit": 136, "sliced": 144, "async": 152}[driver]
    before = _bodies()
    _job(tmp_path, n_v, 2, driver, seed=1)
    first = _bodies()
    assert set(first) - set(before), "the first job built no body"
    hits = _stage_fns.cache_info().hits
    _job(tmp_path, n_v, 2, driver, seed=2)
    second = _bodies()
    assert _stage_fns.cache_info().hits > hits
    assert set(second) == set(first)
    for key, (body, traces) in first.items():
        assert second[key][0] is body
        assert second[key][1] == traces, key


def test_bodies_of_other_layouts_and_k_do_not_collide(tmp_path):
    """Jobs of one stage function on two values of k, and on another
    context size, each get their own bodies and sort right in any order."""
    n_v = 168
    before = set(_bodies())
    _job(tmp_path, n_v, 1, "sliced", seed=3)
    k1 = set(_bodies()) - before
    _job(tmp_path, n_v, 2, "sliced", seed=4)
    k2 = set(_bodies()) - before - k1
    _job(tmp_path, n_v + 8, 1, "sliced", seed=5)
    wider = set(_bodies()) - before - k1 - k2
    assert k1 and k2 and wider
    # k is part of the key: the k = 2 job ran the k = 1 job's stage
    # functions (the lru cache keys them on the shape alone) on new bodies.
    assert {fn for fn, _ in k2} <= {fn for fn, _ in k1}
    assert {key[1] for _, key in k1} == {1} and {key[1] for _, key in k2} == {2}
    _job(tmp_path, n_v, 1, "sliced", seed=6)   # back to the first shape
    assert set(_bodies()) == before | k1 | k2 | wider
