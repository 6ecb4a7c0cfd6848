"""Percent of the job's wall time the executor's round loop stalled waiting
for swap-ins (``TierStats.stall_s``, host clock after the transfer is
complete)."""


def read(run):
    jobs = [j for j in run.jobs if "stall_s" in j.counters]
    if not jobs:
        return None
    return 100.0 * (sum(j.counters["stall_s"] for j in jobs)
                    / sum(j.wall_s for j in jobs))
