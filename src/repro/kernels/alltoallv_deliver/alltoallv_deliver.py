"""Direct message delivery (thesis §6.2) as a Pallas kernel.

The PEMS2 insight: deliver each message's aligned body straight into the
destination context and fix up the unaligned edges from a small cache.  On
TPU the analogue of the disk block is the 128-lane tile: the kernel streams
message tiles HBM→VMEM with a *permuted* ``BlockSpec`` index map (the
sources' messages to destination ``d`` land in ``d``'s slot — the offset
table ``T`` baked into the index map), and the per-message valid length
``counts[s, d]`` is applied as a lane mask — the boundary-block fix-up,
performed while the tile is resident instead of with a read-modify-write
cycle.

Grid: ``(dst, ω/ωt)``.  One grid step moves one ``(S, ωt)`` slab — the
ω-tile of every source's message to one destination — read from the
``[S, dst·ω]`` word view and written as the destination's ``[S, ωt]``
block of the ``[dst, S, ω]`` output, so every block spans whole rows and
whole 128-lane tiles, as the TPU's ``(8, 128)`` tiling requires.  Large
messages stream through VMEM in slab-sized pieces.  The counts ride in
SMEM (scalar prefetch) and the mask is built from them in registers.  A
message width ω that is not a multiple of 128 is padded to one and the
padding sliced off.  For the ``P > 1`` mesh path (:func:`assemble_proc_tiles`)
the destinations are the ``(dst_proc, dst_local)`` pairs of an α-chunk:
source j's tile for destination (p, d) lands at the slot ``all_to_all``
ships straight to process p's context row d — the same offset-table
permutation, now spanning the ``(src_proc, dst_proc)`` tiling of Alg
7.1.3, applied at the sender so the received buffer lands in the
destination rows verbatim.  Two optional extras (both variants):

* ``fill`` — the boundary mask.  When given, lanes past ``counts[s, d]`` are
  overwritten with ``fill`` while the tile is in VMEM (the receiver then
  never needs its own mask pass).  When ``None`` the tile is copied verbatim
  and no counts are passed.
* ``counts_payload`` — the counts matrix itself.  Alltoallv must also hand
  every receiver the transposed counts; ``ct[d, s] = counts_payload[s, d]``
  is returned alongside (a ``[v, v]`` word transpose beside the kernel).

Backend selection — compiled Pallas on TPU, the vectorised fallback on
CPU/GPU, interpret mode for bit-exact kernel emulation in tests — lives in
:mod:`.ops` (``deliver`` / ``deliver_fused``); this module is the kernel
itself and always emits a ``pallas_call``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE_TILE = 128  # TPU lane width: the on-chip analogue of the disk block
# Upper bound on the words of one (S, ωt) slab: a few MiB of VMEM with the
# input and output double-buffered, far below the scoped limit.
_SLAB_WORDS = 1 << 17


def _deliver_kernel(*refs, omega_tile: int, fill, masked: bool, S: int,
                    E: int):
    """One grid step: the ω-tile of every source's message to destination
    ``e``, boundary-masked."""
    if masked:
        cnt_ref, msg_ref, out_ref = refs
    else:
        msg_ref, out_ref = refs
    data = msg_ref[...]                              # (S, ωt)
    if masked:
        e, t = pl.program_id(0), pl.program_id(1)
        src = jax.lax.broadcasted_iota(jnp.int32, (S, 1), 0)
        cnt = jnp.zeros((S, 1), jnp.int32)
        for s in range(S):                           # counts[:, e] from SMEM
            cnt = jnp.where(src == s, cnt_ref[s * E + e], cnt)
        lane = t * omega_tile + jax.lax.broadcasted_iota(
            jnp.int32, data.shape, 1)
        data = jnp.where(lane < cnt, data, jnp.asarray(fill, data.dtype))
    out_ref[...] = data


def _omega_tile(S: int, omega: int) -> int:
    """The widest multiple of 128 lanes dividing ``omega`` (itself a
    multiple of 128) whose ``(S, ωt)`` slab fits ``_SLAB_WORDS``."""
    q = omega // LANE_TILE
    best = 1
    for d in range(1, q + 1):
        if q % d == 0 and S * d * LANE_TILE <= _SLAB_WORDS:
            best = d
    return best * LANE_TILE


def _transpose_tiles(msgs: jnp.ndarray, counts: Optional[jnp.ndarray], *,
                     fill, interpret: bool) -> jnp.ndarray:
    """``out[e, s] = msgs[s, e]`` for ``msgs [S, E, ω]``, lanes at or past
    ``counts[s, e]`` set to ``fill`` when ``fill`` is given."""
    S, E, omega = msgs.shape
    masked = fill is not None
    pad = -omega % LANE_TILE
    if pad:
        msgs = jnp.pad(msgs, ((0, 0), (0, 0), (0, pad)))
    wp = omega + pad
    wt = _omega_tile(S, wp)
    nt = wp // wt
    kernel = functools.partial(_deliver_kernel, omega_tile=wt, fill=fill,
                               masked=masked, S=S, E=E)
    # The scalar-prefetch operand (counts) is passed to every index map
    # after the grid indices; the maps ignore it.
    in_spec = pl.BlockSpec((S, wt), lambda e, t, *_: (0, e * nt + t))
    out_spec = pl.BlockSpec((None, S, wt), lambda e, t, *_: (e, 0, t))
    args = [msgs.reshape(S, E * wp)]
    if masked:
        args.insert(0, counts.astype(jnp.int32).reshape(S * E))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(masked), grid=(E, nt),
            in_specs=[in_spec], out_specs=out_spec),
        # vma: inside shard_map the output varies over the same mesh axes
        # as the messages.
        out_shape=jax.ShapeDtypeStruct((E, S, wp), msgs.dtype,
                                       vma=jax.typeof(msgs).vma),
        interpret=interpret,
        name="alltoallv_deliver",
    )(*args)
    return out[..., :omega] if pad else out


def deliver_tiles(
    msgs: jnp.ndarray,                       # [v, v, ω]  (src, dst, payload)
    counts: Optional[jnp.ndarray] = None,    # [v, v] int32 valid lengths
    counts_payload: Optional[jnp.ndarray] = None,  # [v, v] raw counts words
    *,
    fill=None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Returns ``(out, ct)`` with ``out[d, s] = msgs[s, d]`` (lanes ≥
    ``counts[s, d]`` replaced by ``fill`` when ``fill`` is not ``None``) and
    ``ct[d, s] = counts_payload[s, d]`` (``None`` when no payload given)."""
    v, v2, _ = msgs.shape
    assert v == v2, msgs.shape
    if fill is not None and counts is None:
        raise ValueError("fill requires counts")
    out = _transpose_tiles(msgs, counts, fill=fill, interpret=interpret)
    ct = (None if counts_payload is None
          else jnp.swapaxes(counts_payload, 0, 1))
    return out, ct


def assemble_proc_tiles(
    msgs: jnp.ndarray,                       # [s, P, d, ω]  (src_local, dst_proc, dst_local, payload)
    counts: Optional[jnp.ndarray] = None,    # [s, P, d] int32 valid lengths
    counts_payload: Optional[jnp.ndarray] = None,  # [s, P, d] raw counts words
    *,
    fill=None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """The ``(src_proc, dst_proc)``-tiled grid of the ``P > 1`` mesh path:
    assemble one real processor's α-chunk into the communication buffer in
    destination order, so the subsequent ``all_to_all`` lands each piece
    directly in its destination rows (the sender-side message staging of
    Alg 7.1.3 — the mesh analogue of writing each message straight into the
    destination context).

    ``msgs`` holds the chunk's source-context rows: axis 0 the local source
    contexts, axis 1 the destination real processor, axis 2 its destination
    contexts covered by the chunk.  Returns ``(out, ct)`` with
    ``out[p, d, j] = msgs[j, p, d]`` (lanes ≥ ``counts[j, p, d]`` replaced
    by ``fill`` when given — the boundary fix-up applied while the tile is
    staged) and ``ct[p, d, j] = counts_payload[j, p, d]`` (``None`` when no
    payload given): the transposed counts ride along to the same receiver.
    """
    s, Pn, d, omega = msgs.shape
    if fill is not None and counts is None:
        raise ValueError("fill requires counts")
    out = _transpose_tiles(
        msgs.reshape(s, Pn * d, omega),
        None if counts is None else counts.reshape(s, Pn * d),
        fill=fill, interpret=interpret)
    ct = (None if counts_payload is None
          else jnp.moveaxis(counts_payload, 0, 2))
    return out.reshape(Pn, d, s, omega), ct
