"""Pure-jnp oracle for the tiled k-way merge.

Semantics the kernel must reproduce bit-for-bit: mask every lane at or past
its bucket's count to ``fill``, sort the whole ``v·cap`` population flat, and
keep the lowest ``rcap`` values (``fill``-padded when the population is
smaller than ``rcap``).  This is exactly what PSRS's seed merge stage
computed with ``jnp.sort(recv.reshape(-1))[:rcap]`` on fill-masked buckets.

``exact_starts_ref`` is the splitter search's oracle: the same value-domain
search as ``ops._exact_starts``, each count a scalar binary search
(``jnp.searchsorted``) instead of the fence index.  ``compact_tiles_ref`` is
the compact gather's oracle, and its CPU/GPU form: each output slot's owner
bucket by ``searchsorted`` over the window prefix, and one scalar gather per
lane.  ``unpack_runs_ref`` is the merge input's oracle, in numpy: the runs of a packed receive field.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def kway_merge_ref(buckets: jnp.ndarray, counts: jnp.ndarray, *,
                   rcap: int, fill) -> jnp.ndarray:
    """Lowest ``rcap`` of the masked ``[v, cap]`` buckets, ascending."""
    buckets = jnp.asarray(buckets)
    v, cap = buckets.shape
    lane = jnp.arange(cap, dtype=jnp.int32)
    masked = jnp.where(lane[None, :] < counts[:, None].astype(jnp.int32),
                       buckets, jnp.asarray(fill, buckets.dtype))
    flat = jnp.sort(masked.reshape(-1))
    if flat.shape[0] >= rcap:
        return flat[:rcap]
    pad = jnp.full((rcap - flat.shape[0],), fill, buckets.dtype)
    return jnp.concatenate([flat, pad])


def exact_starts_ref(rows_u32: jnp.ndarray, ranks: jnp.ndarray) -> jnp.ndarray:
    """``starts [R, v]`` for global ``ranks`` over ``v`` ascending uint32
    rows, by binary search per count: ``t = max u: #{x < u} < rank`` found
    MSB first, then the ``rank − #{x < t}`` duplicates of ``t`` assigned
    greedily in bucket order."""
    ranks = ranks.astype(jnp.int32)

    def per_row(vals, side):                  # [R] → [v, R]
        return jax.vmap(lambda row: jnp.searchsorted(row, vals, side=side)
                        )(rows_u32).astype(jnp.int32)

    def bit_step(i, u):
        cand = u | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        return jnp.where(per_row(cand, "left").sum(axis=0) < ranks, cand, u)

    u = jax.lax.fori_loop(0, 32, bit_step,
                          jnp.zeros(ranks.shape, jnp.uint32))
    lo, hi = per_row(u, "left"), per_row(u, "right")
    dups = hi - lo
    need = ranks[None, :] - lo.sum(axis=0, keepdims=True)
    cum = jnp.cumsum(dups, axis=0) - dups
    return (lo + jnp.clip(need - cum, 0, dups)).T


def compact_tiles_ref(masked: jnp.ndarray, starts: jnp.ndarray, tile: int,
                      fill) -> jnp.ndarray:
    """``tiles [G, tile]`` of ``[v, cap]`` rows and window starts ``[G + 1,
    v]``: row ``g`` is the windows ``[starts[g, j], starts[g + 1, j])`` end
    to end in bucket order, ``fill`` past their total — lane by lane."""
    v, cap = masked.shape
    lens = starts[1:] - starts[:-1]
    cum = jnp.cumsum(lens, axis=1) - lens
    slot = jnp.arange(tile, dtype=jnp.int32)
    own = jax.vmap(lambda c: jnp.searchsorted(c, slot, side="right")
                   )(cum).astype(jnp.int32) - 1
    off = slot[None, :] - jnp.take_along_axis(cum, own, axis=1)
    valid = off < jnp.take_along_axis(lens, own, axis=1)
    pos = jnp.take_along_axis(starts[:-1], own, axis=1) + off
    flat = own * cap + jnp.clip(pos, 0, cap - 1)
    return jnp.where(valid, jnp.take(masked.reshape(-1), flat),
                     jnp.asarray(fill, masked.dtype))


def unpack_runs_ref(packed: np.ndarray, offsets: np.ndarray,
                    fill) -> np.ndarray:
    """Oracle for :func:`..ops.unpack_runs`, in numpy: run ``j`` of the
    packed field (``packed[offsets[j]:offsets[j + 1]]``, cut at the field's
    end) as row ``j`` of a ``[v, max run]`` array, ``fill`` past it."""
    packed, offsets = np.asarray(packed), np.asarray(offsets)
    ends = np.minimum(offsets, packed.size)
    runs = [packed[ends[j]:ends[j + 1]] for j in range(offsets.size - 1)]
    out = np.full((len(runs), max(1, max(r.size for r in runs))), fill,
                  packed.dtype)
    for j, r in enumerate(runs):
        out[j, :r.size] = r
    return out
