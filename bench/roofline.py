"""The least bytes a sort's kernels must move, and the chip's peaks.

A sort of ``n`` int32 keys reads each key once and writes it once: the
delivery of the Alltoallv and the k-way merge each move at least
``2 * n * 4`` bytes, whatever the layout they are given.  Counting the live
words, and not the dense ``[v, cap]`` message slabs of today's layout, keeps
a packed layout from reading above 100%.
"""

from __future__ import annotations

import os

from bench import devtrace
from bench.spec import BENCH_DIR, load_json

WORD_BYTES = 4


def sort_pass_bytes(n: int) -> int:
    """Least HBM bytes of one pass over ``n`` keys: each read and written
    once."""
    return 2 * n * WORD_BYTES


def peaks(device_kind: str, path=os.path.join(BENCH_DIR, "peaks.json")):
    """The published peaks of ``device_kind``; a device not in the table is
    an error, never a default."""
    table = load_json(path)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def hbm_share(n: int, kernel_seconds: float, hbm_bytes_per_s: float) -> float:
    """Percent of the HBM roofline: the least time to move
    :func:`sort_pass_bytes` at peak bandwidth over the kernel's time."""
    return 100.0 * sort_pass_bytes(n) / hbm_bytes_per_s / kernel_seconds


def kernel_share(run, kernel: str):
    """:func:`hbm_share` of ``kernel`` over the traced job, or ``None``
    where the trace holds no instance of it (the kernel taken off the path,
    a fallback that never lowered it, or a CPU run): a silent metric, never
    a share of 0."""
    if run.profile is None:
        return None
    lo, hi = run.window_ns
    secs = devtrace.kernel_s(run.profile, kernel, lo, hi)
    if secs <= 0:
        return None
    n = sum(j.n for j in run.jobs)
    return hbm_share(n, secs, run.peaks["hbm_bytes_per_s"])
