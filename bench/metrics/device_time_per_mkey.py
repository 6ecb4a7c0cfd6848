"""Device seconds per million keys sorted in the traced job: the union of
the device's op intervals over the job (averaged over the chips), from the
profiler trace.  Every operation on the device counts, whatever layer
issued it, so a faster splitter search or local sort moves it even where
no kernel's roofline sees the change."""

from bench import devtrace


def read(run):
    if run.profile is None:
        return None
    lo, hi = run.window_ns
    busy = devtrace.busy_s(run.profile, lo, hi)
    if busy <= 0:
        return None
    return busy / (sum(j.n for j in run.jobs) / 1e6)
