"""Percent of the job's wall time spent in the Alltoallv plan stage (the
program's ``stage:alltoallv`` span, host clock)."""


def read(run):
    jobs = [j for j in run.jobs if "stage_s" in j.counters]
    if not jobs:
        return None
    return 100.0 * (sum(j.counters["stage_s"].get("alltoallv", 0.0)
                        for j in jobs) / sum(j.wall_s for j in jobs))
