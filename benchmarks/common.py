"""Benchmark utilities: wall-clock timing with warmup + CSV emission.

All timing goes *through* :data:`TRACER` (the :mod:`repro.obs` span API):
a benchmark's reported number is the very span duration a trace export
would show, so the two can never disagree.  Per-bench scripts time their
phases with ``with TRACER.span(...) as sp: ...`` and read
``sp.duration_s`` instead of hand-rolling ``perf_counter()`` pairs.
"""

from __future__ import annotations

from typing import Callable

import jax

from repro.compile_cache import enable_compile_cache
from repro.obs import Tracer

# Every benchmark imports this module: one persistent compile cache for all.
enable_compile_cache()

# Shared process-wide tracer for every bench script's timed regions.
TRACER = Tracer(name="bench")


def time_fn(fn: Callable, *args, warmup: int = 1, iters: int = 3,
            name: str = "bench") -> float:
    """Median wall time in microseconds (blocks on async dispatch)."""
    for _ in range(warmup):
        _block(fn(*args))
    times = []
    for _ in range(iters):
        with TRACER.span(name, tid="bench") as sp:
            _block(fn(*args))
        times.append(sp.duration_s * 1e6)
    times.sort()
    return times[len(times) // 2]


def _block(out):
    for leaf in jax.tree.leaves(out):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return out


def emit(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.1f},{derived}")
