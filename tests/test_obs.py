"""repro.obs acceptance: tracer ring semantics, Perfetto export balance,
trace round-trips across the PSRS tier × P matrix (valid JSON, balanced
nesting, per-stage span counts, bit-identical results tracing on/off), the
report CLI's overlap cross-check against TierStats, the enriched drain
diagnostics, merge()/snapshot() shard-vs-single-process regression, and the
tracing overhead guard."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.core import PemsConfig
from repro.io import IOEngine, open_file
from repro.obs import NOOP, Tracer, load_trace, summarize, trace_events
from repro.pems_apps import psrs_sort
from repro.pems_apps.psrs import psrs_run_recoverable


# --------------------------------------------------------------------------- #
# Tracer semantics                                                             #
# --------------------------------------------------------------------------- #

def test_tracer_ring_bounds_and_drop_count():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr) == 4
    assert tr.dropped == 6
    assert [e[1] for e in tr.events()] == ["e6", "e7", "e8", "e9"]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_span_records_caller_timings():
    tr = Tracer()
    with tr.span("work", tid="lane", cat="compute", round=3) as sp:
        time.sleep(0.01)
    (ph, name, tid, ts, dur, cat, args), = tr.events()
    assert (ph, name, tid, cat) == ("X", "work", "lane", "compute")
    assert args == {"round": 3}
    assert dur == pytest.approx(sp.duration_s) and dur >= 0.01
    # complete() must bill exactly the caller's readings — the property the
    # stats/trace agreement rests on.
    tr.complete("x", 1.0 + tr.epoch, 3.5 + tr.epoch, tid="lane")
    ev = tr.events()[-1]
    assert ev[3] == pytest.approx(1.0) and ev[4] == pytest.approx(2.5)


def test_noop_tracer_is_inert():
    assert not NOOP.enabled
    with NOOP.span("x", tid="y") as sp:
        pass
    assert sp.duration_s == 0.0
    NOOP.begin("a")
    NOOP.end("a")
    NOOP.instant("b")
    NOOP.counter("c", 1)
    assert NOOP.events() == [] and len(NOOP) == 0


def test_spans_annotate_the_profiler(tmp_path):
    """span() enters a jax.profiler TraceAnnotation of its name, on a
    Tracer and on NOOP alike; complete() stays in the ring only."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("obs:traced", tid="lane", n=3):
            time.sleep(0.002)
        with NOOP.span("obs:noop", tid="lane"):
            time.sleep(0.002)
        t0 = tr.now()
        tr.complete("obs:ring_only", t0, t0 + 0.001, tid="lane")
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [ev for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    names = {ev.name for ev in host}
    assert {"obs:traced", "obs:noop"} <= names
    assert "obs:ring_only" not in names
    assert [e[1] for e in tr.events()] == ["obs:traced", "obs:ring_only"]


def test_tracer_epoch_reads_on_both_clocks():
    tr = Tracer()
    shard = Tracer(epoch=tr.epoch)
    now_ns = time.time_ns()
    since = time.perf_counter() - tr.epoch
    # epoch_time_ns + (perf_counter - epoch) is the wall clock, to within
    # the time between the readings.
    assert abs(tr.epoch_time_ns + since * 1e9 - now_ns) < 5e6
    assert abs(shard.epoch_time_ns - tr.epoch_time_ns) < 5e6


def test_config_rejects_trace_path_without_trace(tmp_path):
    with pytest.raises(ValueError, match="trace_path"):
        PemsConfig(v=4, k=1, trace_path=str(tmp_path / "t.json"))


# --------------------------------------------------------------------------- #
# Export balance sanitation                                                    #
# --------------------------------------------------------------------------- #

def _lane_balance(events):
    """Walk B/E nesting per (pid, tid) in file order; returns the leftover
    open-span count (asserting no orphan E on the way)."""
    stacks = {}
    for e in events:
        key = (e["pid"], e["tid"])
        if e["ph"] == "B":
            stacks.setdefault(key, []).append(e["name"])
        elif e["ph"] == "E":
            assert stacks.get(key), f"orphan E event: {e}"
            stacks[key].pop()
    return sum(len(s) for s in stacks.values())


def test_export_closes_dangling_begin_and_drops_orphan_end():
    tr = Tracer()
    tr.begin("outer", tid="lane")
    tr.begin("inner", tid="lane")
    tr.end("inner", tid="lane")
    # "outer" never ends (e.g. a crash): export must synthesize its close.
    evs = [e for e in trace_events(tr, pid=0) if e["ph"] in ("B", "E")]
    assert _lane_balance(evs) == 0
    assert [e["name"] for e in evs if e["ph"] == "E"][-1] == "outer"

    tr2 = Tracer()
    tr2.end("ghost", tid="lane")      # its B fell off the ring: dropped
    evs2 = [e for e in trace_events(tr2, pid=0) if e["ph"] in ("B", "E")]
    assert evs2 == []


# --------------------------------------------------------------------------- #
# PSRS trace round-trip matrix                                                 #
# --------------------------------------------------------------------------- #

_N, _V, _K = 2048, 8, 2
_STAGES = 7    # sort_sample .. merge — the psrs plan's stage count


def _keys(seed=17):
    rng = np.random.default_rng(seed)
    return rng.integers(-2**31, 2**31 - 1, size=_N, dtype=np.int32)


@pytest.mark.parametrize("tier, P", [
    ("device", 1), ("memmap", 1), ("memmap", 2), ("file", 1), ("file", 2),
])
def test_psrs_trace_roundtrip(tmp_path, tier, P):
    keys = _keys()
    ref = psrs_sort(keys, v=_V, k=_K, tier=tier, P=P,
                    backing_path=(None if tier == "device"
                                  else str(tmp_path / "ref.bin")))
    tp = str(tmp_path / "trace.json")
    out = psrs_sort(keys, v=_V, k=_K, tier=tier, P=P,
                    backing_path=(None if tier == "device"
                                  else str(tmp_path / "ctx.bin")),
                    trace=True, trace_path=tp)
    # Tracing must not perturb the computation.
    np.testing.assert_array_equal(out, ref)

    trace = load_trace(tp)                     # valid JSON by construction
    evs = trace["traceEvents"]
    for e in evs:
        assert {"ph", "pid", "tid", "name"} <= set(e)
    assert _lane_balance(evs) == 0
    # Every call records its four call spans, in order, on the calls lane.
    calls = [e for e in evs if e.get("cat") == "call"]
    assert [e["name"] for e in calls] == [
        "call:prepare", "call:dispatch", "call:wait", "call:extract"]
    assert calls[1]["args"] == {"keys": _N}
    stage = [e for e in evs if e.get("cat") == "stage"]
    sup = [e for e in evs if e.get("cat") == "superstep"]
    if tier == "device":
        # The device tier runs the jitted program: its stages are named
        # scopes on the device's operations, not host spans.
        assert stage == [] and sup == []
    else:
        assert len(stage) == _STAGES
        assert [e["name"] for e in stage] == [
            "stage:sort_sample", "stage:gather_samples",
            "stage:pick_splitters", "stage:bcast_splitters",
            "stage:partition", "stage:alltoallv", "stage:merge"]
        assert len(sup) == 4                   # the four compute supersteps
    pids = {e["pid"] for e in evs}
    # pid 0 is the main tracer; disk tiers add one lane per shard process.
    assert pids == ({0} if tier == "device" else {0, *range(1, P + 1)})
    assert "metrics" in trace
    # The tracers' epoch on both clocks, to place ring events on a
    # profiler trace's clock.
    assert set(trace["clock"]) == {"perf_counter_s", "time_ns"}
    if tier != "device":
        assert any(e.get("cat") == "compute" for e in evs)
        assert any(e.get("cat") == "io" for e in evs)
    if tier == "file":
        # Engine request spans land on the shard engines' worker lanes.
        reqs = [e for e in evs if e.get("cat") == "request"]
        assert reqs and {e["pid"] for e in reqs} <= set(range(1, P + 1))
        assert {e["name"] for e in reqs} >= {"read", "write"}


def test_traced_overhead_is_bounded(tmp_path):
    """Paired min-of-N: tracing must cost ≤ 10% (plus a small absolute
    slack for scheduler noise) on the smoke-sized sort."""
    keys = _keys(3)

    def run(trace):
        t0 = time.perf_counter()
        psrs_sort(keys, v=_V, k=_K, tier="memmap", P=1,
                  backing_path=str(tmp_path / f"b{trace}.bin"), trace=trace)
        return time.perf_counter() - t0

    run(False), run(True)                      # warm both paths (jit etc.)
    plain = min(run(False) for _ in range(3))
    traced = min(run(True) for _ in range(3))
    assert traced <= plain * 1.10 + 0.05, (traced, plain)


# --------------------------------------------------------------------------- #
# Report: span-derived overlap vs TierStats (the acceptance cross-check)       #
# --------------------------------------------------------------------------- #

def test_report_overlap_matches_tierstats(tmp_path):
    tp = str(tmp_path / "trace.json")
    out, pems = psrs_sort(_keys(29), v=_V, k=_K, tier="file", P=2,
                          driver="async",
                          backing_path=str(tmp_path / "ctx.bin"),
                          trace=True, trace_path=tp, return_pems=True)
    trace = load_trace(tp)
    s = summarize(trace)
    stats = pems.merged_shard_stats()
    assert s["metrics_overlap"] == pytest.approx(stats.overlap_fraction)
    # Spans and counters are billed from the same perf_counter readings, so
    # the two overlap fractions must agree (acceptance bound: 5%).
    assert abs(s["overlap_fraction"] - s["metrics_overlap"]) <= 0.05
    # Per-shard engine lanes show I/O overlapping compute in wall time.
    evs = trace["traceEvents"]
    for pid in (1, 2):
        comp = [e for e in evs
                if e["pid"] == pid and e.get("cat") == "compute"]
        ios = [e for e in evs
               if e["pid"] == pid and e.get("cat") in ("io", "request")]
        assert comp and ios
        assert any(c["ts"] < r["ts"] + r.get("dur", 0.0)
                   and r["ts"] < c["ts"] + c.get("dur", 0.0)
                   for c in comp for r in ios)


def test_report_cli(tmp_path):
    tp = str(tmp_path / "trace.json")
    psrs_sort(_keys(5), v=_V, k=_K, tier="file", P=1, driver="async",
              backing_path=str(tmp_path / "ctx.bin"),
              trace=True, trace_path=tp)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.obs", "report", tp, "--top", "3"],
        capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr
    assert "overlap fraction (spans)" in r.stdout
    assert "overlap fraction (TierStats)" in r.stdout
    assert "stage:merge" in r.stdout


# --------------------------------------------------------------------------- #
# Recovery spans                                                               #
# --------------------------------------------------------------------------- #

def test_recoverable_run_traces_cursor_windows(tmp_path):
    tp = str(tmp_path / "trace.json")
    keys = _keys(7)
    out = psrs_run_recoverable(keys, v=_V, state_dir=str(tmp_path / "st"),
                               P=2, tier="file", trace=True, trace_path=tp)
    np.testing.assert_array_equal(out, np.sort(keys))
    evs = load_trace(tp)["traceEvents"]
    assert _lane_balance(evs) == 0
    rec = [e for e in evs if e.get("cat") == "recovery"]
    # 8 stages (load + 7) × 2 processes, begin+end each, plus snapshots.
    assert len([e for e in rec if e["ph"] == "B"]) == 16
    assert any(e["name"] == "snapshot:save" for e in rec)


# --------------------------------------------------------------------------- #
# Drain diagnostics (satellite: age + byte range + instant event)              #
# --------------------------------------------------------------------------- #

def test_drain_timeout_names_age_and_range(tmp_path):
    eng = IOEngine(open_file(str(tmp_path / "d.bin"), 1 << 16, "buffered"),
                   queue_depth=2)
    eng.tracer = Tracer()
    try:
        eng._gate.clear()                      # wedge the workers
        eng.submit_write(0, np.zeros(4096, np.uint8))
        with pytest.raises(TimeoutError) as ei:
            eng.drain(timeout=0.05)
        msg = str(ei.value)
        assert "[0,4096)" in msg and "age=" in msg
        inst = [e for e in eng.tracer.events() if e[0] == "i"]
        assert [e[1] for e in inst] == ["drain_timeout"]
        assert inst[0][6]["in_flight"] == 1
    finally:
        eng._gate.set()
        eng.close()


# --------------------------------------------------------------------------- #
# merge()/snapshot(): per-shard totals equal the single-process run            #
# --------------------------------------------------------------------------- #

def test_shard_merge_equals_single_process_totals(tmp_path):
    keys = _keys(41)
    _, p1 = psrs_sort(keys, v=_V, k=_K, tier="file", P=1,
                      backing_path=str(tmp_path / "p1.bin"),
                      return_pems=True)
    _, p2 = psrs_sort(keys, v=_V, k=_K, tier="file", P=2,
                      backing_path=str(tmp_path / "p2.bin"),
                      return_pems=True)
    merged = p2.shard_ledgers[0].merge(p2.shard_ledgers[1])
    snap1 = p1.ledger.snapshot()
    snap2 = merged.snapshot()
    for key in ("ledger.disk_read_bytes", "ledger.disk_write_bytes",
                "ledger.h2d_bytes", "ledger.d2h_bytes",
                "ledger.syscall_read_bytes", "ledger.syscall_write_bytes"):
        assert snap2[key] == snap1[key], key
    stats = p2.merged_shard_stats()
    assert stats.rounds == p1.tier_stats.rounds
    assert set(stats.snapshot()) == set(p1.tier_stats.snapshot())


def test_metrics_snapshot_subsumes_tierstats(tmp_path):
    _, pems = psrs_sort(_keys(2), v=_V, k=_K, tier="file", P=2,
                        backing_path=str(tmp_path / "m.bin"),
                        trace=True, return_pems=True)
    snap = pems.metrics_snapshot()
    stats = pems.merged_shard_stats()
    for k, val in stats.snapshot().items():
        assert snap[k] == val
    for k in pems.ledger.as_dict():
        assert f"ledger.{k}" in snap
    # Per-shard breakdown rides along at P > 1.
    assert "shard0.tier.rounds" in snap and "shard1.tier.rounds" in snap
