"""The benchmark's copy of the NPB IS key generator."""

import os

import numpy as np
import pytest

from bench import spec

GEN = spec.load_module(os.path.join(spec.BENCH_DIR, "generators",
                                    "npb_is.py"), "npb_is")
SMALL = {"total_keys_log2": 18, "max_key_log2": 11}


def _data_blocks():
    """Every ``data`` block of the benchmark's configuration files: the one
    that runs and the published one it was cut from."""
    blocks = []
    for fn in sorted(os.listdir(os.path.join(spec.BENCH_DIR, "configs"))):
        cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", fn))
        blocks.append(cfg["data"])
        blocks.append(cfg.get("reduced_from", {}).get("data"))
    return [b for b in blocks if b]


@pytest.mark.parametrize("cls,keys_log2,max_key_log2", [("A", 23, 19),
                                                        ("B", 25, 21)])
def test_traffic_files_hold_the_published_classes(cls, keys_log2,
                                                  max_key_log2):
    """The sizes a configuration runs and the ones it was cut from are
    NPB IS's published classes; the traffic mix holds no sizes of its
    own."""
    named = [b for b in _data_blocks() if b["npb_class"] == cls]
    assert named, f"no configuration names NPB class {cls}"
    for b in named:
        assert b["total_keys_log2"] == keys_log2
        assert b["max_key_log2"] == max_key_log2
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                      "npb_is.closed.json"))
    assert mix["generator"] == "npb_is"
    assert "total_keys_log2" not in mix and "max_key_log2" not in mix


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40 + 3])
def test_keys_lie_in_range_with_npb_mean_and_spread(seed):
    keys = GEN.generate(SMALL, seed, 0)
    max_key = 1 << SMALL["max_key_log2"]
    assert keys.dtype == np.int32 and keys.size == 1 << 18
    assert keys.min() >= 0 and keys.max() < max_key
    # Sum of four uniforms times MAX_KEY/4, floored: mean MAX_KEY/2 - 1/2,
    # standard deviation MAX_KEY/4 * sqrt(1/3).
    assert abs(keys.mean() - (max_key / 2 - 0.5)) < 0.01 * max_key
    assert abs(keys.std() - max_key / 4 / np.sqrt(3)) < 0.01 * max_key
    # Bell-shaped and duplicate-heavy: the middle is far denser than the
    # tails, and keys repeat.
    hist = np.bincount(keys, minlength=max_key)
    assert hist[max_key // 2 - 8: max_key // 2 + 8].mean() > 10 * max(
        1, hist[: max_key // 16].mean())
    assert np.unique(keys).size < keys.size


def test_same_seed_same_keys_other_seed_or_set_other_keys():
    a = GEN.generate(SMALL, 2**31 + 1, 0)
    assert np.array_equal(a, GEN.generate(SMALL, 2**31 + 1, 0))
    assert not np.array_equal(a, GEN.generate(SMALL, 2**31 + 2, 0))
    assert not np.array_equal(a, GEN.generate(SMALL, 2**31 + 1, 1))


def test_every_seed_gets_the_same_sizes():
    sizes = {GEN.generate(SMALL, s, i).size for s in (1, 99) for i in (0, 2)}
    assert sizes == {1 << 18}
