"""Public k-way merge wrapper: exact splitting + window gather + dispatch.

``kway_merge(buckets [v, cap], counts [v], rcap=...)`` returns the lowest
``rcap`` elements of the count-masked buckets, ascending, plus the total
received count and an overflow flag — the PSRS merge-stage contract, bit
identical to ``ref.kway_merge_ref`` (and therefore to the seed's dense
``jnp.sort(flat)[:rcap]`` on fill-masked buckets).

Pipeline:

1. **Mask** lanes at/past ``counts[j]`` to ``fill`` — each row is then
   globally ascending (``fill`` is required to be the dtype maximum), and
   the fill lanes become ordinary elements, exactly as the dense re-sort
   treated them.
2. **Exact splitters** (arxiv 0910.2582): for every output tile boundary
   rank ``r = g·tile`` a 32-step MSB-first binary search over the *value
   domain* (order-preserving uint32 bias, so no int64 arithmetic) finds the
   boundary value ``t_r = max u: #{x < u} < r``; duplicates of ``t_r`` are
   then distributed greedily in bucket order, yielding ``starts[g, j]``
   with ``Σ_j (starts[g+1, j] − starts[g, j]) = tile`` exactly.  Each
   per-bucket count ``#{x < u}`` goes through a 128-ary fence index built
   once per call (the first element of every 128-lane block, level over
   level, until at most 128 fences remain): a dense compare against the
   top fences, then per level one gather of a contiguous 128-lane block
   and a dense compare inside it — for ``cap = 2^19`` two block gathers
   where a scalar binary search took 20 dependent scalar gathers.
3. **Compact gather**: tile ``g``'s window lengths sum to exactly ``tile``
   across the buckets, so the windows concatenate in bucket order into one
   dense ``tile``-wide row — ``tiles[G, tile]``, each row a permutation of
   its tile's elements.  Window ``(g, j)`` is a contiguous run: it starts
   at ``starts[g, j]``, has length ``lens[g, j]`` and lands at lane
   ``cum[g, j]`` (the exclusive length prefix), so slot ``s`` of the row is
   lane ``s + starts[g, j] − cum[g, j]`` of bucket ``j`` for the ``j``
   whose ``[cum, cum + lens)`` holds ``s``.  The index arithmetic is per
   ``(g, j)`` and no lane is gathered alone: each window is read as the
   aligned 128-lane blocks of the fence index's first level that cover its
   source span (a row gather of whole blocks, as the splitter search reads
   them), shifted into place by a log-step lane shifter (a static shift
   and a select per bit of the offset within the block), masked to
   ``[cum, cum + lens)`` and summed over the ``v`` buckets — each slot has
   one owner, so the sum is exact.  The ``[·, v, tile]`` windows exist one
   chunk of ``⌈G/v⌉`` tiles at a time (``lax.map``): transient memory
   stays ``O(rcap)`` words.  On CPU/GPU (the dispatch below) the lane
   gather of ``ref.compact_tiles_ref`` stays (each slot's owner by a
   ``searchsorted`` over the window prefix, then one gather per lane):
   there the shifter's passes cost more than the gather.
4. **Tile merge** — a bitonic sorting network over each row, as the Pallas
   grid or one batched jnp expression, backend dispatched like every other
   kernel here; a tile wider than the bitonic kernel's
   ``KERNEL_MAX_N`` takes ``jnp.sort`` (the same size rule as the local
   sort).

Backend selection follows :func:`repro.kernels.alltoallv_deliver.ops.uses_pallas`:
``interpret=None`` (default) compiles the Pallas kernel on TPU and takes
the batched jnp network on CPU/GPU; ``interpret=True`` runs the
kernel's grid machinery in interpret mode (what the equivalence tests
exercise); ``use_kernel=False`` keeps the dense re-sort reference path.

Deliberately NOT jitted: PSRS's merge stage calls this inside the
executor's own (vmapped) trace, and a nested jit boundary would stop XLA
from fusing the mask/gather into the stage body — same reasoning as the
delivery kernel's ``_dispatch``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.alltoallv_deliver.ops import uses_pallas
from repro.kernels.bitonic_sort.ops import KERNEL_MAX_N

from .kway_merge import merge_tile_grid, sort_tile_rows

_SUPPORTED = ("int32", "uint32")


def _materialize(x: jnp.ndarray) -> jnp.ndarray:
    """Fusion barrier: force ``x`` into memory once instead of letting XLA
    re-fuse its producer chain into every consumer (the window gather
    otherwise re-runs inside each tournament stage)."""
    return jax.lax.optimization_barrier(x)


def _to_biased_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Order-preserving map into uint32 so the value-domain binary search
    needs no 64-bit arithmetic: int32 gets the sign-bit bias, uint32 is
    already in order."""
    if x.dtype == jnp.int32:
        return jax.lax.bitcast_convert_type(x, jnp.uint32) ^ jnp.uint32(
            0x80000000)
    return x


_LANES = 128                                  # fence fan-out: the TPU lane width
_U32_MAX = 0xFFFFFFFF


@jax.named_scope("kway_merge.index")
def _fence_index(rows_u32: jnp.ndarray):
    """128-ary fence index over ``v`` ascending uint32 rows ``[v, cap]``.

    Returns ``(top [v, ≤128], levels)``: ``levels[0]`` is the row itself
    viewed as ``[v, nb, 128]`` blocks (also what the compact gather reads
    its windows from), and each ``levels[i + 1]`` holds the first elements
    (fences) of ``levels[i]``'s blocks, again as 128-lane blocks; ``top``
    is the fences of the last level.  Tails are padded with
    ``0xFFFFFFFF``, which no ``x < q`` ever counts."""
    v = rows_u32.shape[0]
    levels = []
    x = rows_u32
    while not levels or x.shape[1] > _LANES:
        nb = -(-x.shape[1] // _LANES)
        x = jnp.pad(x, ((0, 0), (0, nb * _LANES - x.shape[1])),
                    constant_values=jnp.uint32(_U32_MAX))
        levels.append(_materialize(x.reshape(v, nb, _LANES)))
        x = x[:, ::_LANES]
    return _materialize(x), levels


def _count_lt(index, q: jnp.ndarray) -> jnp.ndarray:
    """``[v, R]`` per-row counts ``#{x < q[r]}`` through the fence index:
    one dense compare against the top fences, then per level one gather of
    the chosen 128-lane block and one dense compare inside it.

    With ``j`` fences below ``q``, blocks ``0..j−2`` lie wholly below it,
    so the count is ``(j−1)·128`` plus the count inside block ``j−1``
    (``b = max(j−1, 0)``: for ``j = 0`` block 0 starts at or above ``q``
    and adds nothing)."""
    top, levels = index
    j = (top[:, None, :] < q[None, :, None]).sum(-1, dtype=jnp.int32)
    for blocks in reversed(levels):
        b = jnp.maximum(j - 1, 0)
        blk = jax.vmap(lambda rows, i: rows[i])(blocks, b)   # [v, R, 128]
        j = b * _LANES + (blk < q[None, :, None]).sum(-1, dtype=jnp.int32)
    return j


def _count_le(index, q: jnp.ndarray, cap: int) -> jnp.ndarray:
    """``[v, R]`` per-row counts ``#{x ≤ q[r]}``: ``#{x < q + 1}``, and the
    whole row at ``q = 0xFFFFFFFF`` (the all-fill tail ranks' boundary)."""
    nxt = q + jnp.uint32(1)                  # wraps to 0 only at the max
    return jnp.where(q == jnp.uint32(_U32_MAX), jnp.int32(cap),
                     _count_lt(index, nxt))


def _exact_starts(index, ranks: jnp.ndarray, cap: int) -> jnp.ndarray:
    """Per-bucket window starts for global ``ranks`` over the ``v``
    ascending uint32 rows ``[v, cap]`` of the fence ``index``:
    ``starts[r, j]`` with ``Σ_j starts[r, j] = ranks[r]``.

    For each rank the MSB-first build finds ``t = max u: #{x < u} < rank``
    (so ``#{x ≤ t} ≥ rank > #{x < t}``); the ``rank − #{x < t}`` duplicates
    of ``t`` are assigned greedily in bucket order, which keeps the starts
    monotone across ranks — consecutive boundaries carve consistent,
    disjoint windows.  Every count goes through the one fence index of
    the call; ``ref.exact_starts_ref`` is the same search by scalar binary
    search."""
    ranks = ranks.astype(jnp.int32)

    # lax.fori_loop rather than an unrolled Python loop: the index is a
    # loop-invariant input materialised once, and the trace stays small.
    def bit_step(i, u):
        cand = u | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        below = _count_lt(index, cand).sum(axis=0)
        return jnp.where(below < ranks, cand, u)

    u = jax.lax.fori_loop(0, 32, bit_step,
                          jnp.zeros(ranks.shape, jnp.uint32))

    lo = _count_lt(index, u)                  # [v, R] elements < t per bucket
    hi = _count_le(index, u, cap)             # [v, R] elements <= t
    dups = hi - lo
    need = ranks[None, :] - lo.sum(axis=0, keepdims=True)   # duplicates of t
    cum = jnp.cumsum(dups, axis=0) - dups                   # exclusive prefix
    take = jnp.clip(need - cum, 0, dups)
    return (lo + take).T                                    # [R, v]


def _from_biased_u32(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of :func:`_to_biased_u32`."""
    if jnp.dtype(dtype) == jnp.int32:
        return jax.lax.bitcast_convert_type(x ^ jnp.uint32(0x80000000),
                                            jnp.int32)
    return x


def _compact_blocks(index, starts: jnp.ndarray, tile: int,
                    fill) -> jnp.ndarray:
    """``tiles [G, tile]`` of ``fill``'s dtype: row ``g`` holds the windows
    ``[starts[g, j], starts[g + 1, j])`` of the fence ``index``'s rows
    end to end in bucket order, ``fill`` past their total (the last tile,
    where the ranks stop at ``v·cap``).  Each window is read from the
    index's 128-lane blocks and placed by a log-step shifter;
    ``ref.compact_tiles_ref`` is the same compaction lane by lane."""
    blocks = index[1][0]                                       # [v, nb, 128]
    v, nb, _ = blocks.shape
    lens = starts[1:] - starts[:-1]                          # [G, v]
    cum = jnp.cumsum(lens, axis=1) - lens          # where each window lands
    G = lens.shape[0]
    # Slot s of window (g, j) is lane s + q of row j.  The span [q, q +
    # tile) lies in the nblk blocks from q // 128, shifted left by q % 128;
    # blocks clamped into the row hold only lanes outside the window.
    q = starts[:-1] - cum
    nblk = -(-(tile + _LANES - 1) // _LANES)
    slot = jnp.arange(tile, dtype=jnp.int32)
    bucket = jnp.arange(v, dtype=jnp.int32)[:, None]

    def chunk(q, cum, lens):                          # [C, v] each
        b = jnp.clip(q[..., None] // _LANES
                     + jnp.arange(nblk, dtype=jnp.int32), 0, nb - 1)
        x = blocks[bucket, b].reshape(q.shape + (nblk * _LANES,))
        width = nblk * _LANES
        shift = (q % _LANES)[..., None]
        for bit in reversed(range(_LANES.bit_length() - 1)):
            d = 1 << bit
            width -= d
            x = _materialize(jnp.where((shift >> bit) & 1 == 1,
                                       x[..., d:d + width], x[..., :width]))
        mine = ((cum[..., None] <= slot)
                & (slot < (cum + lens)[..., None]))            # [C, v, tile]
        row = jnp.where(mine, x[..., :tile], 0).sum(axis=1, dtype=x.dtype)
        return jnp.where(slot < lens.sum(axis=1, keepdims=True),
                         _from_biased_u32(row, fill.dtype), fill)

    # The [C, v, ·] windows of C = ⌈G/v⌉ tiles at a time: O(G·tile) words.
    n = min(v, G)
    C = -(-G // n)
    parts = [jnp.pad(a, ((0, n * C - G), (0, 0))).reshape(n, C, v)
             for a in (q, cum, lens)]
    return jax.lax.map(lambda a: chunk(*a), parts).reshape(n * C, tile)[:G]


def unpack_runs(packed: jnp.ndarray, offsets: jnp.ndarray,
                width: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The merge's input from a packed receive field: ``packed [size]``
    holds ``v`` ascending runs end to end, run ``j`` at ``[offsets[j],
    offsets[j + 1])`` (``offsets [v + 1]``; words past ``size`` were never
    stored).  Returns ``(runs [v, width], counts [v])``: row ``j`` starts
    with run ``j``, its lanes past ``counts[j]`` hold what follows it, which
    :func:`kway_merge` masks.  ``width`` must bound every run.  The rows
    are transient, one dynamic slice each: nothing of this shape is
    stored."""
    size = packed.shape[0]
    start = jnp.minimum(offsets[:-1], size)
    counts = jnp.minimum(jnp.minimum(offsets[1:], size) - start, width)
    ext = jnp.concatenate([packed, jnp.zeros((width,), packed.dtype)])
    runs = jax.vmap(lambda s0: jax.lax.dynamic_slice(ext, (s0,), (width,))
                    )(start)
    return runs, counts.astype(jnp.int32)


def kway_merge(
    buckets: jnp.ndarray,                     # [v, cap]; row j ascending in
                                              # its first counts[j] lanes
    counts: jnp.ndarray,                      # [v] valid lanes per bucket
    *,
    rcap: int,
    tile: int = 256,
    fill,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Merge ``v`` sorted buckets into their lowest ``rcap`` elements.

    Returns ``(merged [rcap], total, overflow)`` where ``total`` is
    ``counts.sum()`` and ``overflow`` flags ``total > rcap`` — the stage
    boundary's truncation signal, computed here so no caller can slice
    first and check later.  ``fill`` must be the dtype maximum (the PSRS
    boundary sentinel): masked lanes must sort to every row's tail.

    Works under ``jax.vmap`` (PSRS calls it per resident context) and in
    any enclosing jit trace.  Only 32-bit integer dtypes are supported —
    the splitter search walks the biased uint32 value domain.
    """
    buckets = jnp.asarray(buckets)
    if buckets.ndim != 2:
        raise ValueError(f"buckets must be [v, cap], got {buckets.shape}")
    v, cap = buckets.shape
    if jnp.dtype(buckets.dtype).name not in _SUPPORTED:
        raise ValueError(
            f"kway_merge supports dtypes {_SUPPORTED}, got "
            f"{jnp.dtype(buckets.dtype).name} (the exact-splitter search "
            "runs in the biased uint32 value domain)"
        )
    if tile < 1 or tile & (tile - 1):
        raise ValueError(f"tile={tile} must be a power of two")
    if rcap < 1:
        raise ValueError(f"rcap={rcap} must be >= 1")
    fmax = int(jnp.iinfo(buckets.dtype).max)
    if isinstance(fill, (int, np.integer)) and int(fill) != fmax:
        raise ValueError(
            f"fill={fill!r} must be the dtype maximum {fmax}: masked lanes "
            "must sort to every bucket's tail for the windows to be "
            "ascending"
        )

    counts = jnp.asarray(counts, jnp.int32)
    total = counts.sum()
    overflow = (total > rcap).astype(jnp.int32)

    fill_v = jnp.asarray(fill, buckets.dtype)
    lane = jnp.arange(cap, dtype=jnp.int32)
    masked = jnp.where(lane[None, :] < counts[:, None], buckets, fill_v)

    n_all = v * cap                           # fill lanes are elements too
    G = -(-rcap // tile)
    ranks = jnp.minimum(
        jnp.arange(G + 1, dtype=jnp.int32) * tile, jnp.int32(n_all))

    # The three phases' operations carry their names in the profiler's
    # trace: kway_merge.splitters (the fence index and _exact_starts),
    # kway_merge.gather (the compaction) and kway_merge.tiles.
    with jax.named_scope("kway_merge.splitters"):
        index = _fence_index(_to_biased_u32(masked))
        starts = _exact_starts(index, ranks, cap)          # [G+1, v]

    with jax.named_scope("kway_merge.gather"):
        # Whole blocks on the chip's dispatch, where a lane gather costs a
        # dependent access per lane; on CPU/GPU the one lane gather is
        # cheaper than the shifter's passes.
        if uses_pallas(interpret):
            tiles = _compact_blocks(index, starts, tile, fill_v)
        else:
            from .ref import compact_tiles_ref
            tiles = compact_tiles_ref(masked, starts, tile, fill_v)
        tiles = _materialize(tiles)           # don't re-fuse into the network

    with jax.named_scope("kway_merge.tiles"):
        if use_kernel and uses_pallas(interpret) and tile <= KERNEL_MAX_N:
            merged = merge_tile_grid(tiles, interpret=bool(interpret))
        elif tile <= KERNEL_MAX_N:
            merged = sort_tile_rows(tiles)    # batched over the whole grid
        else:                                 # the bitonic size rule
            merged = jnp.sort(tiles, axis=-1)
    return merged.reshape(G * tile)[:rcap], total, overflow
