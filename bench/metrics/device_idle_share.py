"""Percent of the traced job's wall time in which no operation ran on the
device: 1 - (union of the device's op intervals) / (the job's span), from
the profiler trace."""

from bench import devtrace


def read(run):
    if run.profile is None:
        return None
    lo, hi = run.window_ns
    return 100.0 * (1.0 - devtrace.busy_s(run.profile, lo, hi)
                    / ((hi - lo) / 1e9))
