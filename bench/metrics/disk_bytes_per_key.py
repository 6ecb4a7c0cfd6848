"""Bytes read from and written to the backing per key sorted (the I/O
ledger's ``disk_read_bytes + disk_write_bytes``, an exact count)."""


def read(run):
    jobs = [j for j in run.jobs if "disk_read_bytes" in j.counters]
    if not jobs:
        return None
    return (sum(j.counters["disk_read_bytes"] + j.counters["disk_write_bytes"]
                for j in jobs) / sum(j.n for j in jobs))
