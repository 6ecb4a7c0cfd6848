"""In-VMEM bitonic sorting network (the PSRS local-sort hot spot).

The network sorts every run of ``seg`` consecutive elements of a
row-major ``[R, C]`` block.  Each compare-exchange of stride ``s`` pairs
element ``i`` with ``i ^ s``: for ``s < C`` the partner sits in the same
row, ``s`` lanes away, and is reached with a lane roll; for ``s >= C`` it
sits ``s / C`` rows away and is reached with a sublane roll.  A select on
``(i & s) == 0`` picks the partner, and a select on the stage's direction
bit keeps the minimum or the maximum.  No reshape, gather or scatter
appears, so every stage maps onto whole TPU vector registers; the network
is ``log²(seg)/2`` unrolled vector steps.

:func:`bitonic_network` is the one definition of the network: the Pallas
kernel runs it on each ``(rows, L)`` VMEM block with ``pltpu.roll``, and
the CPU/GPU fallback of the k-way merge runs it on a whole batch with
``jnp.roll``.  Both produce the unique ascending order of each run, so they
are bit-identical on total orders (ints; NaN-free floats).

This is the thesis' "RAM algorithm inside a swapped-in context": the block
is the context, HBM is the external memory, and the sort never touches HBM
until the block swaps back out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128      # TPU lane width
SUBLANES = 8     # TPU sublane count of a 32-bit vector register
# Elements per grid step when several runs share a block: enough vector
# registers per step to amortise the per-step cost, few enough to keep the
# unrolled network small.
_BLOCK_ELEMS = 1 << 15


def bitonic_network(x: jnp.ndarray, seg: int, roll=jnp.roll) -> jnp.ndarray:
    """Sort every run of ``seg`` consecutive elements of ``x`` ascending.

    Runs are taken in row-major order over the last two axes when
    ``seg`` exceeds the last axis (then it must be a whole number of rows),
    otherwise along the last axis (which ``seg`` must divide).  ``seg`` is
    a power of two.  ``roll(x, shift, axis)`` must act like ``jnp.roll``
    with a non-negative shift and axis; the kernel passes ``pltpu.roll``.
    """
    assert seg & (seg - 1) == 0, f"seg={seg} must be a power of two"
    C = x.shape[-1]
    lane_ax = x.ndim - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, lane_ax)
    if seg > C:
        assert x.ndim >= 2 and seg % C == 0, (x.shape, seg)
        R = x.shape[-2]
        row = jax.lax.broadcasted_iota(jnp.int32, x.shape, lane_ax - 1)
        idx = row * C + lane
    else:
        assert C % seg == 0, (x.shape, seg)
        idx = lane
    idx = idx & (seg - 1)                    # position inside its run
    for stage in range(seg.bit_length() - 1):
        # Ascending iff bit (stage+1) of the position is 0: constant within
        # a merge group, alternating between groups; the last stage sorts
        # every run ascending.
        asc = ((idx >> (stage + 1)) & 1) == 0
        for sub in range(stage, -1, -1):
            s = 1 << sub
            if s < C:
                ax, sh, size = lane_ax, s, C
            else:
                ax, sh, size = lane_ax - 1, s // C, R
            nxt = roll(x, size - sh, ax)         # x[i + s]
            prv = roll(x, sh, ax)                # x[i - s]
            low = (idx & s) == 0
            partner = jnp.where(low, nxt, prv)
            x = jnp.where(low == asc, jnp.minimum(x, partner),
                          jnp.maximum(x, partner))
    return x


def _kernel(x_ref, o_ref, *, seg: int):
    o_ref[...] = bitonic_network(x_ref[...], seg, roll=pltpu.roll)


def _layout(n: int) -> tuple:
    """``(L, rows)``: a run of ``n`` elements sits in ``[*, L]`` rows,
    ``rows`` of which (a multiple of 8) make one grid block."""
    L = max(LANES, n // SUBLANES)
    rows = max(SUBLANES, _BLOCK_ELEMS // L)
    return L, rows


def bitonic_sort_rows(x: jnp.ndarray, *, interpret: bool = False,
                      name: str = "bitonic_sort") -> jnp.ndarray:
    """Sort each row of ``[rows, n]`` ascending; ``n`` must be a power of
    two.  The rows are laid out flat as ``[*, L]`` vector rows with ``L =
    max(128, n/8)``, so a row of ``n >= 1024`` spans 8 sublanes and smaller
    rows share a block; padding rows (the dtype maximum) fill the last
    block and are dropped.  ``name`` labels the kernel in the compiled
    program."""
    nrows, n = x.shape
    assert n & (n - 1) == 0, f"n={n} must be a power of two"
    if x.dtype == jnp.uint32:
        # Mosaic has no unsigned min/max: sort the order-preserving int32
        # image (sign bit flipped) and map back.
        y = jax.lax.bitcast_convert_type(x ^ jnp.uint32(1 << 31), jnp.int32)
        y = bitonic_sort_rows(y, interpret=interpret, name=name)
        return jax.lax.bitcast_convert_type(y, jnp.uint32) ^ jnp.uint32(
            1 << 31)
    L, rows = _layout(n)
    total = nrows * n
    # A small batch takes one block just big enough (whole runs, 8-row
    # aligned) instead of a mostly-padding full block.
    rows = min(rows, -(-total // (L * SUBLANES)) * SUBLANES)
    block = rows * L
    nb = -(-total // block)
    flat = x.reshape(-1)
    if nb * block != total:
        flat = jnp.concatenate(
            [flat, jnp.full((nb * block - total,), _max_of(x.dtype),
                            x.dtype)])
    out = pl.pallas_call(
        functools.partial(_kernel, seg=n),
        grid=(nb,),
        in_specs=[pl.BlockSpec((rows, L), lambda b: (b, 0))],
        out_specs=pl.BlockSpec((rows, L), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * rows, L), x.dtype,
                                       vma=jax.typeof(x).vma),
        interpret=interpret,
        name=name,
    )(flat.reshape(nb * rows, L))
    return out.reshape(-1)[:total].reshape(nrows, n)


def _max_of(dtype):
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.iinfo(dtype).max
    return jnp.finfo(dtype).max
