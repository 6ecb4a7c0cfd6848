"""Disk allocated by the backing (``st_blocks * 512`` over its files, read
at the end of each job before they are deleted) per key sorted."""


def read(run):
    jobs = [j for j in run.jobs if "disk_space_bytes" in j.counters]
    if not jobs:
        return None
    return (sum(j.counters["disk_space_bytes"] for j in jobs)
            / sum(j.n for j in jobs))
