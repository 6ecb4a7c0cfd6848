"""The k-way merge's compact gather alone, in its three forms, on one round
of the class-A PSRS merge (NPB IS class A, v=16: k contexts of v runs of
2^15 keys in [v, 2^19] rows, rcap 2^20, tile 256, G = 4096 tiles):

* ``lanes``: ``ref.compact_tiles_ref``, each slot's owner bucket by a
  ``searchsorted`` over the window prefix and one gather per lane (the
  merge's form before the block windows, and its CPU/GPU form);
* ``dense_take``: the owner by a dense compare over the v windows and one
  gather per lane (the alternative weighed against the block windows);
* ``blocks``: ``ops._compact_blocks``, each window read as 128-lane blocks
  and placed by a log-step lane shifter (the merge's form on the chip).

Each form is checked against ``lanes`` and timed as one jitted call over
the k contexts (median of ``--iters``)::

    PYTHONPATH=src python -m benchmarks.bench_compact_gather [--k 4] [--smoke]

``--smoke`` shrinks the rows to [v, 2^11] for a CPU check of the script.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.kway_merge import compact_tiles_ref
from repro.kernels.kway_merge import ops
from .common import emit, time_fn

FILL = np.iinfo(np.int32).max


def _dense_take(masked, starts, tile, fill):
    v, cap = masked.shape
    lens = starts[1:] - starts[:-1]
    cum = jnp.cumsum(lens, axis=1) - lens
    # The owner of slot s is the last window with cum <= s, so its flat
    # source lane s + delta[own] is s plus delta's steps over those windows.
    delta = jnp.arange(v, dtype=jnp.int32) * cap + starts[:-1] - cum
    step = delta - jnp.pad(delta[:, :-1], ((0, 0), (1, 0)))
    slot = jnp.arange(tile, dtype=jnp.int32)
    flat = slot + jnp.where(cum[..., None] <= slot, step[..., None],
                            0).sum(axis=1)
    lanes = jnp.take(masked.reshape(-1), jnp.clip(flat, 0, v * cap - 1))
    return jnp.where(slot < lens.sum(axis=1, keepdims=True), lanes, fill)


def _rounds(k, v, cap, run, seed):
    """``k`` contexts of ``v`` ascending runs of ``run`` keys each, context
    ``c``'s keys in one splitter range (as PSRS delivers them), fill past
    the runs."""
    rng = np.random.default_rng(seed)
    lo = np.arange(k)[:, None, None] * run
    keys = np.sort(lo + rng.integers(0, run, size=(k, v, run)), axis=2)
    rows = np.full((k, v, cap), FILL, np.int32)
    rows[:, :, :run] = keys
    return jnp.asarray(rows)


def run(k: int = 4, cap: int = 1 << 19, run_keys: int = 1 << 15,
        v: int = 16, tile: int = 256, iters: int = 10) -> None:
    rows = _rounds(k, v, cap, run_keys, seed=16)
    G = -(-2 * cap // tile)
    ranks = jnp.minimum(jnp.arange(G + 1, dtype=jnp.int32) * tile,
                        v * run_keys)
    index_of = jax.jit(jax.vmap(
        lambda r: ops._fence_index(ops._to_biased_u32(r))))
    index = index_of(rows)
    starts = jax.jit(jax.vmap(
        lambda ix: ops._exact_starts(ix, ranks, cap)))(index)
    fill = jnp.int32(FILL)
    forms = {
        "lanes": jax.jit(jax.vmap(
            lambda m, s: compact_tiles_ref(m, s, tile, fill))),
        "dense_take": jax.jit(jax.vmap(
            lambda m, s: _dense_take(m, s, tile, fill))),
        "blocks": jax.jit(jax.vmap(
            lambda ix, s: ops._compact_blocks(ix, s, tile, fill))),
    }
    want = np.asarray(forms["lanes"](rows, starts))
    for name, fn in forms.items():
        src = index if name == "blocks" else rows
        same = bool((np.asarray(fn(src, starts)) == want).all())
        us = time_fn(fn, src, starts, iters=iters, name=f"compact_{name}")
        emit(f"compact_{name}_k{k}_cap{cap}_t{tile}", us,
             f"backend={jax.default_backend()};equal={same}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.smoke:
        run(k=args.k, cap=1 << 11, run_keys=1 << 7, iters=args.iters)
    else:
        run(k=args.k, iters=args.iters)


if __name__ == "__main__":
    main()
