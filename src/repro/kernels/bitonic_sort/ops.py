"""Public sort wrapper: pads to a power of two with the dtype's max so the
padding sorts to the tail, then slices it off.

Backend selection mirrors the delivery kernel's tri-state ``interpret``
(:func:`repro.kernels.alltoallv_deliver.ops.uses_pallas`): ``None`` (auto,
the default) compiles the Pallas network on TPU and falls back to
``jnp.sort`` on backends without a native Pallas lowering — interpret-mode
execution would serialise the row grid and the log²(n) stages;
``interpret=True`` runs the kernel bit-exactly anywhere (tests);
``use_kernel=False`` forces the ``jnp.sort`` reference.

Size rule: a row wider than :data:`KERNEL_MAX_N` (after padding) is sorted
by ``jnp.sort`` on every backend.  The kernel holds a whole row in VMEM and
unrolls ``log²(n)/2`` stages over it, so its code grows with ``n``.  Compiled
ahead of time for a TPU v5e (four rows, vmapped), it takes about 2 s at
2^14, 5 s at 2^15, 20 s at 2^16 and 83 s at 2^17, and Mosaic refuses 2^18
(22 MiB of scoped VMEM against a 16 MiB limit).  The bound is the widest
row that compiles in a couple of seconds (``tests/test_tpu_compile.py``
compiles it); which side of it is faster on the chip is not measured.
All paths sort ascending and are bit-identical on total orders (ints;
NaN-free floats).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.alltoallv_deliver.ops import uses_pallas

from .bitonic_sort import _max_of, bitonic_sort_rows

KERNEL_MAX_N = 1 << 14


def sort_path(n: int, *, interpret: Optional[bool] = None,
              use_kernel: bool = True) -> str:
    """Which implementation :func:`sort` takes for rows of ``n`` elements:
    ``"bitonic_kernel"`` or ``"jnp.sort"``."""
    if use_kernel and uses_pallas(interpret) and _next_pow2(n) <= KERNEL_MAX_N:
        return "bitonic_kernel"
    return "jnp.sort"


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def sort(x: jnp.ndarray, *, interpret: Optional[bool] = None,
         use_kernel: bool = True) -> jnp.ndarray:
    """Ascending sort of the last axis of a 1-D or 2-D array."""
    n = x.shape[-1]
    if sort_path(n, interpret=interpret, use_kernel=use_kernel) == "jnp.sort":
        return jnp.sort(x, axis=-1)

    squeeze = x.ndim == 1
    if squeeze:
        x = x[None, :]
    rows = x.shape[0]
    n_pad = _next_pow2(n)
    if n_pad != n:
        x = jnp.concatenate(
            [x, jnp.full((rows, n_pad - n), _max_of(x.dtype), x.dtype)],
            axis=1)
    out = bitonic_sort_rows(x, interpret=bool(interpret))[:, :n]
    return out[0] if squeeze else out


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p
