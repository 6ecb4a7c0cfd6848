"""Tiled k-way merge of pre-partitioned sorted runs (the PSRS merge stage).

Exact splitting (arxiv 0910.2582, §multiway merging) happens in ``ops.py``:
every output tile ``g`` is assigned the per-bucket windows
``[starts[g, j], starts[g+1, j])`` whose union is *exactly* the elements of
global rank ``[g·tile, (g+1)·tile)`` — window lengths sum to ``tile`` across
the buckets, so the windows gather *compactly* into one ``tile``-wide row
per output tile (no per-bucket padding: the gathered traffic is the output
size, not ``v×`` it).  Grid steps therefore merge disjoint output ranges
and never communicate; what is left per tile is ordering its ``tile``
elements.

That ordering is the bitonic sorting network of
:mod:`repro.kernels.bitonic_sort` (one definition, lane and sublane rolls
plus selects), ``log²(tile)/2`` unrolled vector steps per tile, entirely
inside VMEM.  Per output element the work is ``log²(tile)/2`` branchless
vector ops — *constant in both n and v* — so across the grid the merge
costs O(n·log² tile), versus the O(n log n) comparator re-sort of all
``v·cap`` received lanes it replaces (which also paid to re-discover the
order the buckets already had).

``merge_tile_grid`` is the Pallas grid (the bitonic kernel, named
``kway_merge`` in the compiled program); ``sort_tile_rows`` is the same
network as one batched jnp expression (the CPU/GPU fallback — both produce
the unique ascending permutation of each row, so they are bit-identical by
construction).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.bitonic_sort.bitonic_sort import (bitonic_network,
                                                     bitonic_sort_rows)


def sort_tile_rows(x: jnp.ndarray) -> jnp.ndarray:
    """Ascending bitonic sort of the last axis of ``[..., t]``; ``t`` must
    be a power of two.  Pure jnp — the CPU/GPU fallback runs it on the whole
    ``[G, tile]`` batch at once."""
    return bitonic_network(x, x.shape[-1])


def merge_tile_grid(tiles: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """Order each compactly-gathered output tile of ``tiles [G, tile]``
    with the bitonic kernel, entirely in VMEM."""
    return bitonic_sort_rows(tiles, interpret=interpret, name="kway_merge")
