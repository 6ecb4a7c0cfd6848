"""Keys sorted per second of wall time, in millions: all keys of the
window's whole jobs over the time from the first job's start to the last
job's end."""

from bench.window import rate


def read(run):
    return rate(run.jobs) / 1e6
