"""Oracles for direct delivery: masked transpose (+ fused counts), and the
packed Alltoallv in plain numpy."""

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np


def deliver_ref(msgs: jnp.ndarray, counts: jnp.ndarray, *, fill=0) -> jnp.ndarray:
    v, _, omega = msgs.shape
    t = jnp.swapaxes(msgs, 0, 1)                 # [dst, src, ω]
    ct = jnp.swapaxes(counts, 0, 1)              # [dst, src]
    lane = jnp.arange(omega)[None, None, :]
    # Cast fill explicitly: a raw uint32 bit pattern > 2**31 would overflow
    # python-int weak typing against an int32/uint32 payload.
    return jnp.where(lane < ct[..., None], t, jnp.asarray(fill, msgs.dtype))


def deliver_fused_ref(
    msgs: jnp.ndarray,
    counts: Optional[jnp.ndarray] = None,
    counts_payload: Optional[jnp.ndarray] = None,
    *,
    fill=None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Oracle for :func:`..ops.deliver_fused`: plain transpose when ``fill``
    is ``None``, masked transpose otherwise, plus the transposed counts
    payload."""
    if fill is None:
        out = jnp.swapaxes(msgs, 0, 1)
    else:
        out = deliver_ref(msgs, counts, fill=fill)
    ct = None if counts_payload is None else jnp.swapaxes(counts_payload, 0, 1)
    return out, ct


def assemble_proc_ref(
    msgs: jnp.ndarray,                       # [s, P, d, ω]
    counts: Optional[jnp.ndarray] = None,    # [s, P, d]
    counts_payload: Optional[jnp.ndarray] = None,  # [s, P, d]
    *,
    fill=None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Oracle for :func:`..alltoallv_deliver.assemble_proc_tiles`: stage the
    chunk into destination order — ``out[p, d, j] = msgs[j, p, d]`` — with
    the optional source-side boundary mask and transposed counts payload."""
    out = jnp.moveaxis(msgs, 0, 2)           # [P, d, s, ω]
    if fill is not None:
        cm = jnp.moveaxis(counts, 0, 2)      # [P, d, s]
        lane = jnp.arange(msgs.shape[-1])[None, None, None, :]
        out = jnp.where(lane < cm[..., None], out,
                        jnp.asarray(fill, msgs.dtype))
    ct = None
    if counts_payload is not None:
        ct = jnp.moveaxis(counts_payload, 0, 2)
    return out, ct


def deliver_packed_ref(words: np.ndarray, offsets: np.ndarray, size: int,
                       fill) -> Tuple[np.ndarray, np.ndarray]:
    """Oracle for :func:`..ops.deliver_packed`, in numpy: receiver ``d``'s
    row is ``words[s, offsets[s, d]:offsets[s, d + 1]]`` for every sender
    ``s`` in order, cut at ``size`` words and ``fill``-padded; its offsets
    are ``0`` and the running sums of what each sender sent it."""
    words, offsets = np.asarray(words), np.asarray(offsets)
    v = offsets.shape[0]
    packed = np.full((v, size), fill, words.dtype)
    recv_offsets = np.zeros((v, v + 1), np.int32)
    for d in range(v):
        row = np.concatenate([words[s, offsets[s, d]:offsets[s, d + 1]]
                              for s in range(v)])
        packed[d, :min(row.size, size)] = row[:size]
        recv_offsets[d, 1:] = np.cumsum(offsets[:, d + 1] - offsets[:, d])
    return packed, recv_offsets
