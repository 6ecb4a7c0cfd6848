"""Device time by the program's named scopes, and host time by its call
spans: the XSpace reader of each operation's ``tf_op`` path
(:mod:`bench.scopes`), the scope helper on hand-made intervals, the host
span reader and the scopes on traces recorded on the chip with and without
them, and the existing readers unchanged."""

import json
import os
import subprocess
import sys

import pytest

from bench import devtrace, scopes, spec
from bench.devtrace import Profile
from bench.roofline import peaks

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
UNSCOPED = os.path.join(FIXTURES, "tiny_device.xplane.pb.gz")
SCOPED = os.path.join(FIXTURES, "tiny_scoped.xplane.pb.gz")
OLD = ("device_idle_share", "device_time_per_mkey",
       "alltoallv_deliver_roofline", "kway_merge_roofline")


def _reader(name):
    return spec.load_module(
        os.path.join(spec.BENCH_DIR, "metrics", name + ".py"), name)


class _Job:
    def __init__(self, n):
        self.n = n


class _Run:
    def __init__(self, profile, window_ns, jobs):
        self.profile = profile
        self.window_ns = window_ns
        self.jobs = jobs
        self.peaks = peaks("TPU v5 lite")


# --------------------------------------------------------------------------- #
# The XSpace reader, on a protobuf made here                                  #
# --------------------------------------------------------------------------- #

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(num, val):
    return _varint(num << 3) + _varint(val)


def _msg(num, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _entry(num, key, payload):
    return _msg(num, _int(1, key) + _msg(2, payload))


def _plane(name, lines, event_meta=b"", stat_meta=b""):
    return _msg(1, _msg(2, name) + b"".join(_msg(3, ln) for ln in lines)
                + event_meta + stat_meta)


def _line(name, metadata_ids):
    return _msg(2, name) + b"".join(
        _msg(4, _int(1, m) + _int(2, 10 * i) + _int(3, 5))
        for i, m in enumerate(metadata_ids))


def test_op_scopes_are_read_from_str_and_ref_stats_in_event_order():
    # Stat metadata 7 is tf_op; 8 holds an interned path for a ref_value.
    stat_meta = (_entry(5, 7, _int(1, 7) + _msg(2, "tf_op"))
                 + _entry(5, 8, _int(1, 8) + _msg(2, "jit(f)/psrs.merge/x:"))
                 + _entry(5, 9, _int(1, 9) + _msg(2, "flops")))
    event_meta = (
        _entry(4, 1, _int(1, 1) + _msg(2, "%a.1 = add")
               + _msg(5, _int(1, 9) + _int(3, 12))
               + _msg(5, _int(1, 7) + _msg(5, "jit(f)/vmap(psrs.merge)/a:")))
        + _entry(4, 2, _int(1, 2) + _msg(2, "%b.2 = mul")
                 + _msg(5, _int(1, 7) + _int(7, 8)))
        + _entry(4, 3, _int(1, 3) + _msg(2, "%while.3 = while")))
    raw = (_plane("/device:TPU:0",
                  [_line("XLA Modules", [1]), _line("XLA Ops", [1, 2, 3, 1])],
                  event_meta, stat_meta)
           + _plane("/host:CPU", [_line("XLA Ops", [1])], event_meta,
                    stat_meta))
    assert scopes.read_op_scopes(raw) == {"/device:TPU:0": [
        "jit(f)/vmap(psrs.merge)/a:", "jit(f)/psrs.merge/x:", None,
        "jit(f)/vmap(psrs.merge)/a:"]}


# --------------------------------------------------------------------------- #
# The scope helper and the readers on hand-made intervals                     #
# --------------------------------------------------------------------------- #

def test_in_scope_takes_whole_components_bare_or_transformed():
    path = "jit(p)/while/body/vmap(jit(psrs.merge))/kway_merge.splitters/add:"
    for scope in ("psrs.merge", "kway_merge.splitters", "while"):
        assert scopes.in_scope(path, scope)
    for scope in ("psrs", "merge", "kway_merge", "body.x"):
        assert not scopes.in_scope(path, scope)
    # A fused operation joins its sources' paths with ";".
    assert scopes.in_scope("jit(p)/a;vmap(psrs.partition)/b", "psrs.partition")
    assert not scopes.in_scope(None, "psrs.merge")
    assert not scopes.in_scope("", "psrs.merge")


def _profile():
    # The job spans [0, 60) ns.  Chip 0: three merge ops, one of them in
    # the splitter search, an op of a scope that only starts alike, and a
    # loop with no path; chip 1: one fused merge op running past the job.
    ops = {"/device:TPU:0": [("%a", 0, 10), ("%b", 5, 20), ("%c", 30, 40),
                             ("%d", 45, 55), ("%while.1", 0, 60)],
           "/device:TPU:1": [("%e", 10, 70)]}
    op_scopes = {"/device:TPU:0": [
        "jit(p)/vmap(psrs.merge)/kway_merge.splitters/add:",
        "jit(p)/psrs.merge/x:", "jit(p)/vmap(jit(psrs.merge))/y:",
        "jit(p)/psrs.merge_extra/q:", None],
        "/device:TPU:1": ["jit(p)/psrs.merge/z:;jit(p)/other/w:"]}
    host = [(devtrace.JOB_SPAN, 0, 60), ("call:prepare", -5, 10),
            ("call:dispatch", 10, 40), ("call:wait", 40, 50),
            ("call:dispatch", 58, 90)]
    return Profile(ops=ops, host=host), op_scopes


def test_scope_time_is_the_union_of_its_ops_averaged_over_chips():
    prof, op_scopes = _profile()
    lo, hi = prof.window()
    # Chip 0: [0,20) + [30,40) = 30 ns; chip 1: [10,60) = 50 ns.
    assert scopes.scope_s(prof, op_scopes, "psrs.merge", lo, hi) == (
        pytest.approx(40e-9))
    assert scopes.scope_s(prof, op_scopes, "kway_merge.splitters", lo,
                          hi) == pytest.approx(5e-9)
    # Several scopes: the union of their ops, each interval once.
    assert scopes.scope_s(prof, op_scopes,
                          ("psrs.merge", "psrs.merge_extra"), lo, hi) == (
        pytest.approx((40 + 50) / 2 * 1e-9))
    # An op without a path counts for no scope, the loop's own included.
    assert scopes.scope_s(prof, op_scopes, "while", lo, hi) == 0.0
    assert scopes.scope_s(Profile(), {}, "psrs.merge", lo, hi) == 0.0
    # Paths that do not match the ops one for one are refused.
    with pytest.raises(ValueError, match="tf_op paths"):
        scopes.scope_s(prof, {}, "psrs.merge", lo, hi)


def test_host_prep_reader_on_hand_made_intervals():
    prof, _ = _profile()
    run = _Run(prof, prof.window(), [_Job(1_000_000), _Job(1_000_000)])
    # call:prepare clipped to [0, 10) and both dispatches: 10 + 30 + 2 ns
    # over the window's two jobs.
    assert _reader("host_prep_s").read(run) == pytest.approx(42e-9 / 2)
    # No call span in the window: silent, never 0.
    run.profile = Profile(ops=prof.ops, host=[
        h for h in prof.host if not h[0].startswith("call:")])
    assert _reader("host_prep_s").read(run) is None
    run.profile = None
    assert _reader("host_prep_s").read(run) is None


# --------------------------------------------------------------------------- #
# Traces recorded on the chip                                                 #
# --------------------------------------------------------------------------- #

def _fixture_run(path):
    prof = devtrace.load(path)
    return _Run(prof, prof.window(), [_Job(4096)])


def test_existing_readers_read_as_before_on_the_unscoped_trace():
    """The values the readers gave on this trace before the program had
    scopes (the same computation)."""
    run = _fixture_run(UNSCOPED)
    before = {"device_idle_share": 99.76696022014013,
              "device_time_per_mkey": 0.580500732421875,
              "alltoallv_deliver_roofline": 11.05242210214586,
              "kway_merge_roofline": 0.3574534799407488}
    for name in OLD:
        assert _reader(name).read(run) == pytest.approx(before[name],
                                                        rel=1e-12)
    lo, hi = run.window_ns
    assert devtrace.busy_s(run.profile, lo, hi) == pytest.approx(
        0.002377731, rel=1e-12)
    assert devtrace.idle_gaps(run.profile, lo, hi)[0] == [
        "lower_sharding_computation", pytest.approx(1.010086993)]


def test_the_unscoped_trace_has_paths_but_no_scope_and_no_call_span():
    run = _fixture_run(UNSCOPED)
    op_scopes = scopes.load_op_scopes(UNSCOPED)
    (plane, paths), = op_scopes.items()
    assert len(paths) == len(run.profile.ops[plane])
    assert sum(p is not None for p in paths) > len(paths) // 2
    lo, hi = run.window_ns
    for scope in scopes.SCOPES:
        assert scopes.scope_s(run.profile, op_scopes, scope, lo, hi) == 0.0
    assert _reader("host_prep_s").read(run) is None


def test_the_scoped_trace_holds_every_scope_and_call_span():
    """A tiny device-tier job traced on a TPU v5e by the harness, with the
    program's scopes and call spans."""
    run = _fixture_run(SCOPED)
    op_scopes = scopes.load_op_scopes(SCOPED)
    (plane, paths), = op_scopes.items()
    assert len(paths) == len(run.profile.ops[plane])
    lo, hi = run.window_ns
    secs = {s: scopes.scope_s(run.profile, op_scopes, s, lo, hi)
            for s in scopes.SCOPES}
    assert all(v > 0 for v in secs.values()), secs
    vals = {n: _reader(n).read(run) for n in OLD + ("host_prep_s",)}
    assert all(v is not None and v > 0 for v in vals.values()), vals
    # The splitter search lies inside the merge, the merge inside the
    # device's busy time; the host's share is at most the job.
    busy = devtrace.busy_s(run.profile, lo, hi)
    assert secs["kway_merge.splitters"] <= secs["psrs.merge"] <= busy
    assert vals["host_prep_s"] <= (hi - lo) / 1e9
    calls = [n for n, _, _ in run.profile.host if n.startswith("call:")]
    assert calls == ["call:prepare", "call:dispatch", "call:wait",
                     "call:extract"]


def test_the_scope_summary_runs_from_the_command_line():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out = subprocess.run([sys.executable, "-m", "bench.scopes", SCOPED],
                         cwd=root, capture_output=True, text=True,
                         check=True, timeout=300)
    summary = json.loads(out.stdout)
    assert set(summary["scopes_s"]) == set(scopes.SCOPES)
    # Nearly all of the tiny job's device time lies under a stage or
    # collective scope; the rest is the executor's loops and the result's
    # slices.
    assert 0.9 <= summary["covered_share"] <= 1.0
    assert summary["covered_s"] <= summary["busy_s"]
