"""The measured window: whole jobs back to back, and the rate over them.

The window starts with the first job and ends at the end of the first job
that finishes once ``seconds`` have passed, so it holds whole jobs only.
A rate is all keys of all its jobs over the time from the first job's
start to the last job's end.
"""

from __future__ import annotations

import sys
import time
import traceback
from dataclasses import dataclass, field


@dataclass
class Job:
    n: int                  # keys sorted
    t0: float               # host clock at the call
    t1: float               # host clock at its return
    key_set: int            # which of the run's key sets it sorted
    counters: dict = field(default_factory=dict)
    ok: bool = True         # False when the call raised

    @property
    def wall_s(self) -> float:
        return self.t1 - self.t0


def run_window(job_fn, key_sets, seconds: float, max_jobs=None,
               clock=time.perf_counter):
    """Run ``job_fn(keys) -> (out, counters)`` over the key sets in turn
    until a job ends ``seconds`` or more after the first began (or after
    ``max_jobs`` jobs).  Returns the jobs and their outputs (``None`` for a
    job that raised; its traceback goes to standard error)."""
    jobs, outputs = [], []
    start = None
    while True:
        i = len(jobs) % len(key_sets)
        keys = key_sets[i]
        t0 = clock()
        start = t0 if start is None else start
        try:
            out, counters = job_fn(keys)
            ok = True
        except Exception:        # a failed job is counted, not fatal
            traceback.print_exc(file=sys.stderr)
            out, counters, ok = None, {}, False
        t1 = clock()
        jobs.append(Job(n=int(keys.size), t0=t0, t1=t1, key_set=i,
                        counters=counters, ok=ok))
        outputs.append(out)
        if t1 - start >= seconds or (max_jobs and len(jobs) >= max_jobs):
            return jobs, outputs


def rate(jobs) -> float:
    """Keys per second over the window: all keys of all jobs over the time
    from the first job's start to the last job's end.  A job that raised
    sorted nothing, and its time still counts."""
    span = jobs[-1].t1 - jobs[0].t0
    return sum(j.n for j in jobs if j.ok) / span
