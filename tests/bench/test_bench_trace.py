"""The reduction from a profiler trace to busy time, kernel time and the
breakdown: on hand-made intervals, and on a trace recorded on the chip."""

import os

import pytest

from bench import devtrace, spec
from bench.devtrace import Profile

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "tiny_device.xplane.pb.gz")


def test_union_merges_overlapping_and_touching_intervals():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        (0, 4), (5, 7), (9, 9)]


MERGE = "%vmap_kway_merge_.{} = s32[4,256]{{1,0}} custom-call(s32[4] %p)"
FUSION = "%fusion.1 = u32[72]{0} fusion(u32[2,4] %g), kind=kCustom"


def _profile():
    # Two device planes; the job spans [0, 100) ns.  On chip 0 a loop runs
    # [10, 40) with a fusion and a merge kernel nested in it.
    ops = {"/device:TPU:0": [("%while.5 = (s32[]) while(s32[] %t)", 10, 40),
                             (FUSION, 10, 20), (MERGE.format(1), 20, 40),
                             (MERGE.format(2), 60, 70),
                             ("%copy.3 = s32[8]{0} copy(s32[8] %a)", 95, 120)],
           "/device:TPU:1": [(FUSION, 0, 50)]}
    host = [(devtrace.JOB_SPAN, 0, 100), ("TransferToDevice", 0, 12),
            ("outer", 0, 100), ("PjitFunction(program)", 38, 62)]
    return Profile(ops=ops, host=host)


def test_busy_time_is_the_union_within_the_window_averaged_over_chips():
    prof = _profile()
    lo, hi = prof.window()
    assert (lo, hi) == (0, 100)
    # Chip 0: [10,40) + [60,70) + [95,100) = 45 ns; chip 1: 50 ns.
    assert devtrace.busy_s(prof, lo, hi) == pytest.approx(47.5e-9)


def test_kernel_time_counts_only_the_kernels_instances():
    prof = _profile()
    assert devtrace.kernel_s(prof, "kway_merge", 0, 100) == pytest.approx(
        30e-9)
    assert devtrace.is_kernel(
        "%alltoallv_deliver.12 = s32[16,64]{1,0} custom-call(s32[16,4] %x)",
        "alltoallv_deliver")
    assert not devtrace.is_kernel(FUSION, "kway_merge")
    # A fusion named after the kernel is not the kernel.
    assert not devtrace.is_kernel(
        "%kway_merge_reshape.2 = s32[8]{0} fusion(s32[8] %x)", "kway_merge")
    assert devtrace.op_name(FUSION) == "fusion.1"


def test_top_ops_rank_by_self_time():
    top = devtrace.top_ops(_profile(), 0, 100)
    # fusion.1: 10 ns on chip 0 and 50 on chip 1, averaged over the chips;
    # the loop's own event has no time left once its body is taken out.
    assert top[0] == [FUSION, pytest.approx(30e-9)]
    assert top[1] == [MERGE.format(1), pytest.approx(10e-9)]
    assert dict(top)["%while.5 = (s32[]) while(s32[] %t)"] == 0
    assert len(top) <= 10


def test_idle_gaps_go_to_the_shortest_host_span_covering_them():
    prof = _profile()
    gaps = dict(devtrace.idle_gaps(prof, 0, 100))
    # Chip 0 idles [0,10) (TransferToDevice covers it), [40,60)
    # (PjitFunction covers all of it), [70,95) (only "outer").
    assert gaps == {"TransferToDevice": pytest.approx(10e-9),
                    "PjitFunction(program)": pytest.approx(20e-9),
                    "outer": pytest.approx(25e-9)}
    extra = [("jax: lower", 70, 90)]
    gaps = dict(devtrace.idle_gaps(prof, 0, 100, extra))
    assert gaps["jax: lower"] == pytest.approx(25e-9)


def test_device_time_per_mkey_is_busy_time_over_the_keys():
    reader = spec.load_module(os.path.join(spec.BENCH_DIR, "metrics",
                                           "device_time_per_mkey.py"),
                              "device_time_per_mkey")
    run = type("R", (), {"profile": _profile(), "window_ns": (0, 100),
                         "jobs": [type("J", (), {"n": 2_000_000})()]})
    # 47.5 ns of busy time (averaged over the chips) over 2 Mkeys.
    assert reader.read(run) == pytest.approx(47.5e-9 / 2)
    run.profile = None
    assert reader.read(run) is None


def test_a_trace_without_one_job_span_is_refused():
    with pytest.raises(ValueError):
        Profile(ops={}, host=[]).window()


def test_the_recorded_chip_trace_reduces_to_sane_numbers():
    """A tiny device-tier job traced on a TPU v5e by the harness."""
    prof = devtrace.load(FIXTURE)
    lo, hi = prof.window()
    window_s = (hi - lo) / 1e9
    assert list(prof.ops) == ["/device:TPU:0"]
    busy = devtrace.busy_s(prof, lo, hi)
    assert 0 < busy < window_s
    for kernel in ("alltoallv_deliver", "kway_merge"):
        assert 0 < devtrace.kernel_s(prof, kernel, lo, hi) < busy
    top = devtrace.top_ops(prof, lo, hi)
    assert 1 <= len(top) <= 10
    assert sum(s for _, s in top) <= busy * (1 + 1e-9)
    gaps = devtrace.idle_gaps(prof, lo, hi)
    assert 1 <= len(gaps) <= 10
    assert sum(s for _, s in gaps) <= window_s - busy + 1e-9
