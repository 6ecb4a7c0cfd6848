"""The roofline byte functions at the cell shapes, and the peaks table."""

import pytest

from bench import roofline
from bench.devtrace import Profile


def test_least_bytes_at_the_cell_shapes():
    # NPB IS class A: 2^23 int32 keys, each read once and written once.
    assert roofline.sort_pass_bytes(1 << 23) == 2 * (1 << 23) * 4 == 67108864
    # Class B and C, for the cells kept for later.
    assert roofline.sort_pass_bytes(1 << 25) == 268435456
    assert roofline.sort_pass_bytes(1 << 27) == 1073741824


def test_peaks_of_the_v5e_and_refusal_of_an_unknown_chip():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_hbm_share_arithmetic():
    # 67108864 B at 819 GB/s is 81.94 us; a kernel taking 8.194 ms is at 1%.
    least = (1 << 26) / 819e9
    assert roofline.hbm_share(1 << 23, least * 100, 819e9) == pytest.approx(
        1.0)
    assert roofline.hbm_share(1 << 23, least, 819e9) == pytest.approx(100.0)


class _Run:
    def __init__(self, ops):
        self.profile = None if ops is None else Profile(
            ops={"/device:TPU:0": ops})
        self.window_ns = (0, 10**9)
        self.jobs = [type("J", (), {"n": 1 << 23})()]
        self.peaks = roofline.peaks("TPU v5 lite")


def test_kernel_share_reads_the_kernels_operations():
    least_ns = (1 << 26) / 819e9 * 1e9
    merge = "%vmap_kway_merge_.{} = s32[4,256]{{1,0}} custom-call(s32[4] %p)"
    ops = [(merge.format(1), 0, least_ns * 10),
           ("%fusion.3 = s32[8]{0} fusion(s32[8] %x)", 0, 5),
           (merge.format(2), 10**6, 10**6 + least_ns * 10)]
    share = roofline.kernel_share(_Run(ops), "kway_merge")
    assert share == pytest.approx(5.0)


def test_kernel_share_is_silent_off_the_path_and_loud_when_lost():
    """No trace, or a trace with no instance of the kernel (taken off the
    path, or a fallback that never lowered it), leaves the metric out of
    the result line: never a share of 0.  A cell that lists the metric and
    lacks it in its traced line is what is refused."""
    assert roofline.kernel_share(_Run(None), "kway_merge") is None
    assert roofline.kernel_share(_Run([]), "kway_merge") is None
    assert roofline.kernel_share(
        _Run([("%fusion.1 = s32[8]{0} fusion()", 0, 9)]),
        "kway_merge") is None
