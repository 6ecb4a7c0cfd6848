"""Chrome/Perfetto ``trace_event`` JSON export.

Produces the JSON *object* format (``{"traceEvents": [...], ...}``), which
both ``chrome://tracing`` and https://ui.perfetto.dev load directly and
which permits extra top-level keys — the flat metrics snapshot rides along
under ``"metrics"`` so one file carries spans *and* the ``TierStats``/
``IOLedger`` counters they must agree with, and the tracers' epoch under
``"clock"`` as both a ``perf_counter`` and a ``time.time_ns()`` reading, so
ring events can be placed on a ``jax.profiler`` trace's clock
(``time_ns + ts``).

Lane layout: each tracer becomes one Perfetto *process* (``pid``) — the
executor's main tracer is pid 0, shard ``p``'s engine/round tracer pid
``p+1`` — and each distinct ``tid`` string inside a tracer becomes one
named *thread* lane; the lanes are merged in memory and written once.
Timestamps are exported in microseconds since the shared epoch, as the
format requires.

Balance sanitation: ``B``/``E`` events are matched per lane on export —
an orphan ``E`` (its ``B`` fell off the ring) is dropped, and a ``B``
still open at the end of the buffer is closed at the last seen timestamp —
so every exported trace nests cleanly no matter where the ring wrapped or
where a crash cut the run.
"""

from __future__ import annotations

import json
from typing import Dict, Iterable, List, Optional

__all__ = ["trace_events", "write_trace", "load_trace"]

_US = 1e6


def _balanced(events: list) -> list:
    """Drop orphan E events and close dangling B events per (tid, lane)."""
    out = []
    stacks: Dict[str, List[int]] = {}       # tid -> indices of open B's
    last_ts: Dict[str, float] = {}
    for ev in events:
        ph, name, tid, ts = ev[0], ev[1], ev[2], ev[3]
        last_ts[tid] = max(last_ts.get(tid, ts), ts)
        if ph == "B":
            stacks.setdefault(tid, []).append(len(out))
        elif ph == "E":
            if not stacks.get(tid):
                continue                    # orphan end: B fell off the ring
            stacks[tid].pop()
        out.append(ev)
    for tid, open_bs in stacks.items():
        for i in reversed(open_bs):         # close innermost first
            b = out[i]
            out.append(("E", b[1], tid, last_ts[tid], None, None, None))
    return out


def trace_events(tracer, pid: int,
                 process_name: Optional[str] = None) -> List[dict]:
    """Convert one tracer's ring into Chrome trace_event dicts under
    ``pid``, with process/thread metadata and balanced B/E nesting."""
    name = process_name or getattr(tracer, "name", f"pid{pid}")
    out: List[dict] = [{
        "ph": "M", "pid": pid, "tid": 0, "name": "process_name",
        "args": {"name": name},
    }]
    tids: Dict[str, int] = {}
    for ev in _balanced(tracer.events()):
        ph, ev_name, tid_s, ts, dur, cat, args = ev
        tid = tids.get(tid_s)
        if tid is None:
            tid = tids[tid_s] = len(tids) + 1
            out.append({"ph": "M", "pid": pid, "tid": tid,
                        "name": "thread_name", "args": {"name": tid_s}})
        rec = {"ph": ph, "pid": pid, "tid": tid, "name": ev_name,
               "ts": ts * _US}
        if ph == "X":
            rec["dur"] = dur * _US
        if ph == "i":
            rec["s"] = "t"                  # thread-scoped instant
        if cat is not None:
            rec["cat"] = cat
        if args is not None:
            rec["args"] = args
        out.append(rec)
    return out


def write_trace(path: str, events: Iterable[dict],
                metrics: Optional[dict] = None, tracer=None) -> str:
    """Write one Perfetto-loadable JSON object trace file.  ``tracer``
    (any tracer on the events' shared epoch) adds its epoch under
    ``"clock"``: ``perf_counter_s`` and the same instant as ``time_ns``."""
    doc = {"traceEvents": list(events), "displayTimeUnit": "ms"}
    if metrics is not None:
        doc["metrics"] = metrics
    if tracer is not None:
        doc["clock"] = {"perf_counter_s": tracer.epoch,
                        "time_ns": tracer.epoch_time_ns}
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def load_trace(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
