"""Host seconds per job before the device can start: the program's
``call:prepare`` (keys to the device, the plan built) and ``call:dispatch``
(the jitted program's trace, lowering, compile or cache load, and enqueue)
spans, as the profiler's host plane records them within the traced window,
over the window's jobs.  None where the program records no such span."""

SPANS = ("call:prepare", "call:dispatch")


def read(run):
    if run.profile is None:
        return None
    lo, hi = run.window_ns
    secs = sum(min(e, hi) - max(s, lo) for name, s, e in run.profile.host
               if name in SPANS and e > lo and s < hi) / 1e9
    if secs <= 0:
        return None
    return secs / len(run.jobs)
