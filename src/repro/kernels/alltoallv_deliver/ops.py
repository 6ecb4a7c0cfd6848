"""Public wrappers for the direct-delivery kernel.

Backend selection (``interpret`` tri-state) makes the kernel path the
default rather than an opt-in:

* ``interpret=None`` (auto, the default) — compiled Pallas on TPU; on
  backends without a native Pallas lowering (CPU, and GPU in this repo's
  toolchain) the vectorised reference path is used instead, because
  interpret-mode execution serialises the (v, v, ω/ωt) grid and is far
  slower than one fused XLA transpose.
* ``interpret=True``  — Pallas interpret mode: bit-exact emulation of the
  kernel's grid/index-map machinery on any backend (what the equivalence
  tests run).
* ``interpret=False`` — force the compiled Pallas kernel.

``use_kernel=False`` bypasses the kernel entirely (pure-jnp reference),
which is what the seed implementation did; it is kept so equivalence can be
asserted end-to-end (``psrs_sort(..., use_kernel=...)``).

The packed Alltoallv (MPI's displacement form: each sender's messages are
contiguous slices of one field, each receiver's arrive concatenated into
one field) reaches the kernel through transient tiles:
:func:`stage_slices` cuts every ``(sender, destination)`` slice into a
``width``-lane tile, the kernel delivers and masks the tiles, and
:func:`pack_slices` lays each receiver's tiles end to end.  Neither side
keeps a ``[v, ω]`` slab: the tiles live for the one delivery.
:func:`deliver_packed` is the three in a row, the single-process form.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .alltoallv_deliver import assemble_proc_tiles, deliver_tiles


def check_fill_range(fill, dtype) -> None:
    """Reject a ``fill`` value the payload dtype cannot represent.

    The kernels bake ``fill`` into the trace with ``jnp.asarray(fill,
    msgs.dtype)``, which wraps silently for out-of-range integers — a
    ``fill=INT_MAX`` boundary sentinel on an ``int8``/``uint16`` payload
    would arrive as ``-1``/``65535`` and corrupt every masked lane.  Checked
    here, once, for every delivery path (kernel, vectorised fallback, and
    the collective layer's word-level fill patterns)."""
    dt = jnp.dtype(dtype)
    if not isinstance(fill, (int, float, np.integer, np.floating)):
        return                                 # traced/abstract: can't check
    if jnp.issubdtype(dt, jnp.integer):
        info = jnp.iinfo(dt)
        if isinstance(fill, (float, np.floating)) and not float(fill).is_integer():
            raise ValueError(
                f"fill={fill!r} is not representable in integer payload "
                f"dtype {dt.name}"
            )
        if not info.min <= int(fill) <= info.max:
            raise ValueError(
                f"fill={fill!r} out of range for payload dtype {dt.name} "
                f"[{info.min}, {info.max}]: the cast would wrap silently"
            )
    elif jnp.issubdtype(dt, jnp.floating):
        try:
            f = float(fill)
        except OverflowError:
            # An integer too large even for float64 certainly overflows the
            # payload dtype; keep the advertised exception type.
            raise ValueError(
                f"fill={fill!r} overflows payload dtype {dt.name}"
            ) from None
        if math.isfinite(f) and abs(f) > float(jnp.finfo(dt).max):
            raise ValueError(
                f"fill={fill!r} overflows payload dtype {dt.name} "
                f"(max {float(jnp.finfo(dt).max):g}): the cast would "
                "produce inf"
            )


def uses_pallas(interpret: Optional[bool] = None) -> bool:
    """Whether delivery would emit a ``pallas_call`` for this ``interpret``
    setting on the current backend.  The single source of truth for the
    backend dispatch — the collective layer consults it too, so its
    CPU-fallback heuristics can never desync from the kernel dispatch."""
    if interpret is None:
        return jax.default_backend() == "tpu"
    return True


def _dispatch(msgs, counts, counts_payload, *, fill, interpret, use_kernel):
    # Deliberately NOT jitted: the collective layer calls this inside its own
    # trace, and a nested jit boundary stops XLA from fusing the delivery
    # transpose into the store-row rebuild (~1.4× regression at small ω).
    # Direct (eager) calls from tests trace per-op, which is fine there.
    if use_kernel and uses_pallas(interpret):
        return deliver_tiles(
            msgs, counts, counts_payload, fill=fill,
            interpret=bool(interpret),
        )
    # Vectorised reference path: one fused transpose(+mask), the CPU/GPU
    # fallback.  Semantically identical to the kernel.
    from .ref import deliver_fused_ref
    return deliver_fused_ref(msgs, counts, counts_payload, fill=fill)


def deliver(msgs: jnp.ndarray, counts: jnp.ndarray, *, fill=0,
            interpret: Optional[bool] = None,
            use_kernel: bool = True) -> jnp.ndarray:
    """PEMS2 direct delivery of ``msgs [v, v, ω]`` with valid lengths
    ``counts [v, v]`` → ``[v(dst), v(src), ω]``, lanes past the count set to
    ``fill``."""
    check_fill_range(fill, msgs.dtype)
    out, _ = _dispatch(
        msgs, counts.astype(jnp.int32), None, fill=fill, interpret=interpret,
        use_kernel=use_kernel,
    )
    return out


def deliver_fused(
    msgs: jnp.ndarray,                        # [v, v, ω] payload (any 4-byte dtype)
    counts: Optional[jnp.ndarray] = None,     # [v, v] int32 mask lengths
    counts_payload: Optional[jnp.ndarray] = None,  # [v, v] raw counts words
    *,
    fill=None,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Delivery with the optional fusions the collective layer uses: the
    boundary mask only when ``fill`` is given, and the counts transpose as a
    second output of the same kernel call.  Returns ``(out, ct)``."""
    if fill is not None and counts is None:
        raise ValueError("fill requires counts")
    if fill is not None:
        check_fill_range(fill, msgs.dtype)
    return _dispatch(
        msgs,
        None if fill is None else counts.astype(jnp.int32),
        counts_payload,
        fill=fill, interpret=interpret, use_kernel=use_kernel,
    )


def assemble_proc_fused(
    msgs: jnp.ndarray,                        # [s, P, d, ω] pre-all_to_all chunk
    counts: Optional[jnp.ndarray] = None,     # [s, P, d] int32 mask lengths
    counts_payload: Optional[jnp.ndarray] = None,  # [s, P, d] raw counts words
    *,
    fill=None,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Mesh-path staging with the same fusions as :func:`deliver_fused`,
    over the ``(src_proc, dst_proc)``-tiled grid: the α-chunk ``[s, P, d,
    ω]`` is assembled into destination order as ``out[p, d, j] = msgs[j, p,
    d]`` (boundary mask applied at the source; transposed counts payload as
    the fused second output) so the subsequent ``all_to_all`` lands every
    piece directly in its destination rows.  Same backend dispatch as the
    ``P == 1`` route: compiled Pallas on TPU, the vectorised reference on
    CPU/GPU, interpret mode for tests."""
    if fill is not None and counts is None:
        raise ValueError("fill requires counts")
    if fill is not None:
        check_fill_range(fill, msgs.dtype)
    if use_kernel and uses_pallas(interpret):
        return assemble_proc_tiles(
            msgs,
            None if fill is None else counts.astype(jnp.int32),
            counts_payload, fill=fill, interpret=bool(interpret),
        )
    from .ref import assemble_proc_ref
    return assemble_proc_ref(
        msgs,
        None if fill is None else counts.astype(jnp.int32),
        counts_payload, fill=fill,
    )


def stage_slices(words: jnp.ndarray, offsets: jnp.ndarray,
                 width: int) -> jnp.ndarray:
    """Sender side of the packed delivery: ``words [m, n]`` holds each
    sender's messages as contiguous slices, message ``d`` of sender ``s``
    at ``[offsets[s, d], offsets[s, d + 1])`` (``offsets [m, v + 1]``).
    Returns the tiles ``[m, v, width]``: tile ``[s, d]`` starts at the
    message's first word.  Lanes past the message length hold whatever
    follows it; the delivery masks them.  ``width`` must bound every
    message.  One dynamic slice per tile with scalar starts, in a loop, so
    every copy is a contiguous block move."""
    m, n = words.shape
    v = offsets.shape[1] - 1
    # A tail of width lanes keeps every slice in bounds (offsets <= n), so
    # no start is clamped.
    ext = jnp.concatenate([words, jnp.zeros((m, width), words.dtype)], axis=1)

    def tile(i, tiles):
        s, d = i // v, i % v
        blk = lax.dynamic_slice(ext, (s, offsets[s, d]), (1, width))
        return lax.dynamic_update_slice(tiles, blk[None], (s, d, 0))

    return lax.fori_loop(0, m * v, tile,
                         jnp.zeros((m, v, width), words.dtype))


def pack_slices(tiles: jnp.ndarray, counts: jnp.ndarray, size: int,
                fill) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Receiver side of the packed delivery: receiver ``d``'s delivered
    tiles ``tiles[d, s]`` (valid in their first ``counts [m, v]`` lanes,
    ``fill`` past them, as the masked delivery leaves them) laid end to
    end in source order.  Returns ``(packed [m, size], offsets [m, v + 1])``
    with source ``s``'s words at ``[offsets[d, s], offsets[d, s + 1])`` and
    ``fill`` past ``offsets[d, v]``.  Words past ``size`` are dropped; the
    offsets still count them, so a caller sees the overflow."""
    m, v, width = tiles.shape
    zero = jnp.zeros((m, 1), jnp.int32)
    offsets = jnp.concatenate(
        [zero, jnp.cumsum(counts.astype(jnp.int32), axis=1)], axis=1)
    start = jnp.minimum(offsets, size)
    # Tile s is written whole, its fill tail over where tiles s+1.. land
    # next; writing in source order leaves each word to its own tile.
    buf = jnp.full((m, size + width), fill, tiles.dtype)

    def place(i, buf):
        d, s = i // v, i % v
        blk = lax.dynamic_slice(tiles, (d, s, 0), (1, 1, width))[0]
        return lax.dynamic_update_slice(buf, blk, (d, start[d, s]))

    buf = lax.fori_loop(0, m * v, place, buf)
    return buf[:, :size], offsets


def deliver_packed(
    words: jnp.ndarray,                       # [v, n] each sender's slices
    offsets: jnp.ndarray,                     # [v, v + 1] slice offsets
    size: int,                                # words of each receive field
    *,
    fill,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The packed Alltoallv among ``v`` contexts of one process: receiver
    ``d`` gets ``words[s, offsets[s, d]:offsets[s, d + 1]]`` for ``s = 0,
    1, ...`` end to end.  Returns ``(packed [v, size], recv_offsets [v, v
    + 1])`` as :func:`pack_slices` does.  The tiles go through the
    delivery kernel (:func:`deliver_fused`, masked with ``fill``), with the
    same backend dispatch."""
    check_fill_range(fill, words.dtype)
    width = min(words.shape[1], size)       # bounds every message that lands
    counts = (offsets[:, 1:] - offsets[:, :-1]).astype(jnp.int32)
    tiles = stage_slices(words, offsets, width)
    out, _ = deliver_fused(tiles, counts, None, fill=fill,
                           interpret=interpret, use_kernel=use_kernel)
    return pack_slices(out, counts.T, size, fill)
