"""EM collective communication (thesis §2.2, §6.2, §7).

Message model: a sending context holds a field of shape ``[v, ω]`` (one padded
message per destination, ω the thesis' per-message bound) plus a ``[v]`` count
field; after Alltoallv the receiving context's ``[v, ω]`` field holds message
``recv[s] = send_of_s[ρ]``.  The destination slot offsets are static layout
offsets — the thesis' shared offset table ``T`` (§6.2) made trace-time.

Two Alltoallv implementations are provided:

* ``mode="direct"``   — PEMS2 (Alg 7.1.1/7.1.2): messages move straight from
  source contexts to destination contexts; with ``P > 1`` the network phase is
  α-chunked (Alg 7.1.3) so the communication buffer stays ≤ α·k·ω per
  destination process.
* ``mode="indirect"`` — PEMS1 baseline (Alg 2.2.1): messages are staged
  through a separate "indirect area" (an extra ``[v, v, ω]`` buffer behind an
  optimization barrier so XLA cannot fuse the copy away), costing the extra
  write+read the thesis eliminates.

Direct mode with ``P == 1`` routes through the fused *word-level* delivery
path by default (``use_kernel=True``): the send field's raw word range is
sliced straight out of the ``[v, words]`` context store
(:meth:`ContextStore.field_words_view`), handed to the Pallas direct-delivery
kernel (:mod:`repro.kernels.alltoallv_deliver` — compiled on TPU, vectorised
fallback elsewhere, interpret mode for tests), and the delivered ``[v(dst),
v(src), ω]`` block is written back into the recv word range
(:meth:`ContextStore.with_field_words`; on CPU, cache-sized ω instead takes
a row-at-a-time in-place loop, ``_deliver_rows_inplace``).  This collapses
the seed's dense gather→bitcast→reshape→transpose→scatter round-trip into
slice → deliver → store-row rebuild, fuses the counts transpose into the
same kernel call, and — when the caller passes ``fill`` — also fuses the
receiver's boundary mask (lanes past ``counts[s, d]`` arrive as ``fill``,
the thesis' boundary-block fix-up), so applications like PSRS no longer
re-mask downstream.  ``use_kernel=False`` keeps the seed's dense-transpose
path; both are bit-identical (and ≈1.6–2.8× apart in wall time on CPU at
v=16, ω ≥ 256 — see ``benchmarks/bench_alltoallv.py``).

With ``P > 1`` the same word-level route runs per mesh process
(``_alltoallv_fused_mesh``): the send field's raw word range crosses the
network directly and the (src_proc, dst_proc)-tiled kernel delivers it
into the destination rows, boundary mask and counts transpose fused — the
dense ``[m, v, ω]`` per-process transposed staging of ``_global_transpose``
never materializes.  Unchunked (``alpha=None``) this is a single
``lax.all_to_all`` feeding one concat row rebuild; with ``alpha`` set the
network phase is α-chunked (Alg 7.1.3) into one ``[k, P, α, ω]`` buffer per
(source round, destination chunk) — ≤ α·k·ω words per process pair, the
Lemma 7.1.9 bound — delivered in place chunk by chunk.  ``use_kernel=False``
keeps the dense route for equivalence testing.

The packed form (``offsets=``: 1-D ``send``/``recv`` fields with ``[v +
1]`` offset fields, MPI's displacement form) moves variable-length messages without a
``[v, ω]`` slab in any context: each sender's messages are contiguous
slices of one field, and each receiver's arrive end to end in one field.
On the device it stages transient tiles through the same delivery kernel
(:func:`repro.kernels.alltoallv_deliver.deliver_packed`, or the
assemble-and-ship route over the mesh); on a backing tier it reads each
message's live words and writes each receiver's live words, nothing else.

The I/O ledger is updated with *event-level* counts that tests validate
against the closed forms in :mod:`repro.core.analysis`; the delivery
implementation (kernel vs dense, masked vs not) never changes the event
counts — they model the simulated external-memory traffic, not the host
execution strategy.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax
from jax.sharding import PartitionSpec as P

from .backing import TieredStore
from .context import ContextStore, WORD, _from_words, _to_words


# --------------------------------------------------------------------------- #
# Alltoallv                                                                    #
# --------------------------------------------------------------------------- #

def alltoallv(
    self,
    store: ContextStore,
    send: str,
    recv: str,
    send_counts: Optional[str] = None,
    recv_counts: Optional[str] = None,
    mode: str = "direct",
    fill=None,
    use_kernel: bool = True,
    procs: Optional[list] = None,
    offsets: Optional[Tuple[str, str]] = None,
) -> ContextStore:
    """Every VP ρ sends message ``send[d]`` to VP d; after the call VP ρ holds
    ``recv[s] =`` (s's message to ρ) and transposed counts.

    ``send``/``recv`` name ``[v, ω]`` layout fields (``ω`` the per-message
    payload; all byte math below is ``ω`` words × 4 bytes).

    ``offsets=(send_off, recv_off)`` selects the packed form instead, in
    place of the counts: ``send``/``recv`` are 1-D fields and the two names
    ``[v + 1]`` offset fields.  VP ρ's message to d is ``send[send_off[d]:
    send_off[d + 1]]``; after the call VP ρ's ``recv`` holds the messages
    from VP 0, 1, ... end to end, ``recv_off[s]`` where s's message starts
    and ``recv_off[v]`` the total.  A total past the ``recv`` field is
    recorded in full but only the field's words are stored: the caller
    checks it.  Words past the total are ``fill`` on the device tiers and
    left as they were on the backing tiers.

    ``fill``
    (optional, requires counts) fuses the receiver's boundary mask into
    delivery: lanes past ``send_counts[ρ][d]`` arrive as ``fill`` instead of
    whatever padding the sender left.  ``use_kernel=False`` keeps the seed's
    dense-transpose implementation (bit-identical, for equivalence testing);
    the ledger is unaffected by either knob.

    Sharding/mesh semantics: on the device tier with ``P > 1`` the network
    phase runs over the jax mesh (α-chunked ``lax.all_to_all``, Alg 7.1.3).
    On a backing tier the collective is host-side data movement over the
    (possibly sharded) backing: each destination shard's recv rows are
    staged through a bounded host buffer and written back to that shard
    only, with measured disk bytes billed to the owning shard's ledger.
    ``procs`` (tiered stores only) restricts the *destination* side to the
    listed processes' shards — sources are still read from every shard, but
    nothing outside the listed shards is written (per-process recovery).
    In-place shuffles (``send == recv``) are not per-process recoverable:
    a rerun would re-read already-shuffled source rows.

    Raises ``ValueError`` for unknown ``mode``, mismatched field shapes,
    ``fill`` without counts, ``offsets`` with counts, ``procs`` on a device
    store, or a staging chunk that cannot fit ``device_cap_bytes``.
    """
    if mode not in ("direct", "indirect"):
        raise ValueError(f"unknown mode {mode!r}")
    if procs is not None and not isinstance(store, TieredStore):
        raise ValueError("procs= requires a backing-tier store")
    if offsets is not None:
        if send_counts is not None or recv_counts is not None:
            raise ValueError("offsets= replaces send_counts/recv_counts")
        return _alltoallv_packed(self, store, send, recv, *offsets, mode,
                                 fill, use_kernel, procs)
    cfg = self.cfg
    f = store.layout.field(send)
    if store.layout.field(recv).shape != f.shape:
        raise ValueError("send/recv field shapes must match")
    if f.shape[0] != cfg.v:
        raise ValueError(f"alltoallv fields must be [v, ω]; got {f.shape}")
    if fill is not None and (send_counts is None or recv_counts is None):
        raise ValueError("fill requires send_counts/recv_counts")
    if fill is not None:
        # One early representability check for every implementation path:
        # an out-of-range fill would otherwise wrap silently (or fail deep
        # inside a trace with an opaque cast error).
        from repro.kernels.alltoallv_deliver import check_fill_range
        check_fill_range(fill, f.dtype)
    omega_b = int(_np.prod(f.shape[1:], dtype=_np.int64)) * WORD if len(f.shape) > 1 else WORD

    if isinstance(store, TieredStore):
        store = _alltoallv_host(self, store, send, recv,
                                send_counts, recv_counts, fill, procs)
    else:
        # The device paths' operations carry the collective's name in the
        # profiler's trace, in a jitted program as in an eager one.
        with jax.named_scope("pems.alltoallv"):
            if mode != "direct" or not use_kernel:
                store = _alltoallv_dense(self, store, send, recv,
                                         send_counts, recv_counts, mode,
                                         fill)
            elif cfg.P == 1:
                store = _alltoallv_fused(self, store, send, recv,
                                         send_counts, recv_counts, fill)
            else:
                store = _alltoallv_fused_mesh(self, store, send, recv,
                                              send_counts, recv_counts,
                                              fill)

    _ledger_alltoallv(self, omega_b, mode)
    return store


def _fill_word(fill, dtype) -> _np.uint32:
    """The word-level masking convention, in one place: the bit pattern of
    ``fill`` in the payload field's dtype, as a store word — what every
    raw-word delivery path (P == 1 fused, mesh, tiered host) writes into
    masked lanes so the receiver reads the typed value."""
    return _np.asarray(fill, _np.dtype(dtype)).view(_np.uint32)


# CPU-fallback implementation switch: below this per-message word count the
# whole store is cache-resident and a row-at-a-time fori_loop delivery (one
# strided gather + one in-place row write per destination, ~2 payload copies
# of traffic) beats the vectorised transpose+concat (~4 copies); above it the
# loop's strided gathers thrash and the single fused transpose wins.
_ROW_LOOP_MAX_WW = 768

# Mesh-path landing switch: up to this many per-process payload words the
# received buffer is cache-resident and a dynamic-update-slice write wins;
# above it the concat row rebuild (which fuses the lane split into its
# output loop) is consistently faster on CPU.
_MESH_DUS_MAX_WORDS = 1 << 17


def _alltoallv_fused(self, store, send, recv, send_counts, recv_counts, fill):
    """PEMS2 word-level direct delivery (Alg 7.1.1/7.1.2): slice the send
    field's word range out of the store, deliver through the Pallas kernel
    (counts transpose and boundary mask fused), write the recv range back.
    On backends without compiled Pallas the delivery is a vectorised
    transpose — or, for cache-sized ω, a row-at-a-time in-place loop."""
    from repro.kernels.alltoallv_deliver import deliver_fused, uses_pallas

    cfg = self.cfg
    lo = store.layout
    v = cfg.v
    ww = lo.field_words(send) // v             # ω in store words

    cnt_mask = None
    cnt_words = None
    if send_counts is not None and recv_counts is not None:
        cnt_words = store.field_words_view(send_counts)      # [v, v] raw bits
        if fill is not None:
            cnt_mask = store.field(send_counts).reshape(v, v)

    fill_word = None
    if fill is not None:
        fill_word = int(_fill_word(fill, lo.field(send).dtype))

    # The row loop writes destination rows while later iterations still read
    # source rows, so it must not run when send and recv alias the same
    # field; the vectorised path reads the whole block before writing.
    if not uses_pallas() and ww <= _ROW_LOOP_MAX_WW and send != recv:
        store = _deliver_rows_inplace(store, send, recv, cnt_mask, fill_word)
        ct = None if cnt_words is None else jnp.swapaxes(cnt_words, 0, 1)
    else:
        W = store.field_words_view(send).reshape(v, v, ww)
        out, ct = deliver_fused(W, cnt_mask, cnt_words, fill=fill_word)
        store = store.with_field_words(recv, out.reshape(v, v * ww))
    if cnt_words is not None:
        cs = lo.field(send_counts).dtype
        cr = lo.field(recv_counts).dtype
        if cs == cr:
            store = store.with_field_words(recv_counts, ct)
        else:
            store = store.with_field(
                recv_counts, _from_words(ct, cs).astype(cr)
            )
    return store


def _deliver_rows_inplace(store, send, recv, counts_i32, fill_word):
    """Row-at-a-time direct delivery: for each destination d, gather column
    d's message from every source context and write it straight into d's
    recv word range.  The fori_loop carry lets XLA update the store buffer
    in place — the closest host analogue of the thesis writing each message
    directly into the destination context on disk."""
    lo = store.layout
    v = store.v
    off_s = lo.offset(send)
    off_r = lo.offset(recv)
    ww = lo.field_words(send) // v
    nw = v * ww

    def body(d, dat):
        col = lax.dynamic_slice(dat, (0, off_s + d * ww), (v, ww))
        if fill_word is not None:
            cnt = lax.dynamic_slice(counts_i32, (0, d), (v, 1))
            lane = lax.broadcasted_iota(jnp.int32, (v, ww), 1)
            col = jnp.where(lane < cnt.astype(jnp.int32),
                            col, jnp.uint32(fill_word))
        return lax.dynamic_update_slice(dat, col.reshape(1, nw), (d, off_r))

    return ContextStore(store.layout, lax.fori_loop(0, v, body, store.data))


def _alltoallv_fused_mesh(self, store, send, recv, send_counts, recv_counts,
                          fill):
    """PEMS2 word-level direct delivery over the ``P > 1`` mesh: assemble →
    ship → land, Alg 7.1.3's structure at the word level.

    Each chunk is *assembled* straight from the send field's raw word range
    by the (src_proc, dst_proc)-tiled kernel — destination-ordered staging
    with the receiver's boundary mask applied at the source and the counts
    transpose fused into the same pass — then *shipped* through
    ``lax.all_to_all`` (payload and transposed counts as two aligned
    buffers of the same collective round), and the received buffer *lands*
    in the destination rows verbatim: no receive-side transpose exists, and
    the dense ``[m, v, ω]`` per-process staging of ``_global_transpose``
    never materializes.

    Default (``alpha=None``, unchunked): a single all_to_all feeding one
    concat-based row rebuild (whose output loop XLA fuses the lane split
    into — the ``with_field_words`` trick).  With ``alpha`` set the network
    phase is α-chunked: one ``[k, P, α, ω]`` buffer per (source round of k
    (§6.5), destination α-chunk) — ≤ α·k·ω payload words per (source,
    destination) process pair, the Lemma 7.1.9 bound — landed in place
    chunk by chunk.  Bounded buffers cost extra collective launches; the
    knob exists for memory-bounded staging (and the tiered ``P > 1`` path
    to come), not for speed.
    """
    cfg = self.cfg
    lo = store.layout
    v, Pn, m, k = cfg.v, cfg.P, cfg.v_local, cfg.k
    alpha = cfg.alpha
    ww = lo.field_words(send) // v             # ω in store words
    off_s, off_r = lo.offset(send), lo.offset(recv)
    has_counts = send_counts is not None and recv_counts is not None
    if has_counts:
        off_c, off_rc = lo.offset(send_counts), lo.offset(recv_counts)
        cs = lo.field(send_counts).dtype
        cr = lo.field(recv_counts).dtype

    fill_word = None
    if fill is not None:
        fill_word = int(_fill_word(fill, lo.field(send).dtype))

    def conv_ct(ct):
        if cs == cr:
            return ct
        return _to_words(_from_words(ct, cs).astype(cr))


    def f(local):                              # [m, words]: this proc's rows
        # Word-level send matrix: W[sl, dp, dl] is row sl's ω-words for
        # global destination dp·m + dl (sliced once; functional, so the
        # recv writes below cannot corrupt it even when send == recv).
        W = lax.slice(local, (0, off_s), (m, off_s + v * ww))
        W = W.reshape(m, Pn, m, ww)
        C_w = C_i = None
        if has_counts:
            C_w = lax.slice(local, (0, off_c), (m, off_c + v))
            C_w = C_w.reshape(m, Pn, m)
            if fill is not None:
                C_i = _from_words(C_w, cs).astype(jnp.int32)

        if alpha is None:
            # Unchunked: one assembly, one all_to_all, one row landing.
            pay, ct = _ship_chunk(cfg, W, C_i, C_w, fill_word)  # [m, P, m, ww]
            if m * v * ww <= _MESH_DUS_MAX_WORDS:
                new = lax.dynamic_update_slice(
                    local, pay.reshape(m, v * ww), (0, off_r))
            else:
                left = lax.slice(local, (0, 0), (m, off_r))
                right = lax.slice(
                    local, (0, off_r + v * ww), (m, local.shape[1]))
                new = jnp.concatenate(
                    [left, pay.reshape(m, v * ww), right], axis=1)
            if has_counts:
                # After the landing: `new` has a single consumer, so XLA
                # updates it in place (before it, the update would copy the
                # whole row block — `local` is still pinned by the slices).
                new = lax.dynamic_update_slice(
                    new, conv_ct(ct.reshape(m, v)), (0, off_rc))
            return new

        for s0 in range(0, m, k):              # source rounds of k (§6.5)
            for c0 in range(0, m, alpha):      # destination α-chunks
                c1 = min(c0 + alpha, m)
                xc = W[s0:s0 + k, :, c0:c1, :]          # [k, P, c, ww]
                cm = cp = None
                if has_counts:
                    cp = C_w[s0:s0 + k, :, c0:c1]
                    if fill is not None:
                        cm = C_i[s0:s0 + k, :, c0:c1]
                pay, ct = _ship_chunk(cfg, xc, cm, cp, fill_word)  # [c, P, k, ww]
                if has_counts:
                    ct = conv_ct(ct)
                # Land in place: each source process' slots are a
                # contiguous word range of the destination rows.
                for q in range(Pn):
                    local = lax.dynamic_update_slice(
                        local, pay[:, q].reshape(c1 - c0, k * ww),
                        (c0, off_r + (q * m + s0) * ww),
                    )
                    if has_counts:
                        local = lax.dynamic_update_slice(
                            local, ct[:, q], (c0, off_rc + q * m + s0),
                        )
        return local

    data = jax.shard_map(
        f,
        mesh=self.mesh,
        in_specs=(P(cfg.vp_axis, None),),
        out_specs=P(cfg.vp_axis, None),
    )(store.data)
    return ContextStore(lo, data)


def _ship_chunk(cfg, xc, cm, cp, fill_word, use_kernel=True):
    """Assemble one chunk ``[s, P, d, ww]`` into destination order (mask +
    counts transpose fused), all_to_all payload and counts, returning
    payload ``[d, P, s, ww]`` and counts ``[d, P, s]`` (or None) — both
    already in the destination rows' slot order."""
    from repro.kernels.alltoallv_deliver import assemble_proc_fused

    out, ct = assemble_proc_fused(xc, cm, cp, fill=fill_word,
                                  use_kernel=use_kernel)
    y = lax.all_to_all(out, cfg.vp_axis, split_axis=0,
                       concat_axis=1, tiled=False)      # [d, P(src), s, ww]
    if ct is None:
        return y, None
    yc = lax.all_to_all(ct, cfg.vp_axis, split_axis=0,
                        concat_axis=1, tiled=False)     # [d, P(src), s]
    return y, yc


def _alltoallv_dense(self, store, send, recv, send_counts, recv_counts,
                     mode, fill):
    """Dense-transpose data path: the PEMS1 indirect baseline, the α-chunked
    ``P > 1`` network path, and the ``use_kernel=False`` reference."""
    cfg = self.cfg
    f = store.layout.field(send)

    M = store.field(send)                      # [v, v, ω...]
    M = M.reshape(cfg.v, cfg.v, -1)

    if mode == "indirect":
        # PEMS1: stage every message in the indirect area first.  The barrier
        # forces the staging copy to materialise.
        M = jax.lax.optimization_barrier(M)

    Mt = _global_transpose(self, M)            # [v, v, ω] with axes (dst, src)
    Ct = None
    if send_counts is not None and recv_counts is not None:
        C = store.field(send_counts).reshape(cfg.v, cfg.v, 1)
        if mode == "indirect":
            C = jax.lax.optimization_barrier(C)
        Ct = _global_transpose(self, C)        # transposed once, reused below
    if fill is not None:
        lane = jax.lax.broadcasted_iota(jnp.int32, Mt.shape, 2)
        Mt = jnp.where(lane < Ct[..., 0][..., None].astype(jnp.int32),
                       Mt, jnp.asarray(fill, Mt.dtype))
    store = store.with_field(recv, Mt.reshape((cfg.v,) + f.shape))
    if Ct is not None:
        store = store.with_field(
            recv_counts, Ct.reshape(cfg.v, cfg.v).astype(
                store.layout.field(recv_counts).dtype)
        )
    return store


def _alltoallv_host(self, store, send, recv, send_counts, recv_counts, fill,
                    procs=None):
    """Backing-tier Alltoallv: pure host-side data movement over the
    host/memmap store — messages move straight between context rows of the
    backing array, the closest real-world analogue of the thesis writing
    each message directly into the destination context on disk.  Bit-
    identical to the device paths (copies only, no arithmetic).

    The staging is chunked *per destination process, then by α* (the α knob,
    Alg 7.1.3 applied host-side): each chunk stages ``[αd, v, ω]`` — every
    source's messages for αd of process p's destination contexts — masks it
    in place, and writes it straight into those destinations' recv word
    ranges, which live entirely in shard p.  This is the per-process host
    buffer of the parallel disk model: sources are read from every shard
    (and billed to each source shard's ledger), but each chunk writes one
    destination shard only, so a ``procs`` subset re-runs without touching
    the other shards' bytes.  ``device_cap_bytes`` (the memory budget the
    backing tier exists to honour) bounds the staging buffer *per process*:
    αd is clamped so the chunk fits, instead of materializing the dense
    ``[v, v, ω]`` matrix the tier cannot afford.  An in-place shuffle
    (``send == recv``) additionally snapshots the whole field — a chunked
    in-place transpose would read rows it has already overwritten — and
    raises when snapshot + chunk cannot fit the cap."""
    cfg = self.cfg
    v, m = cfg.v, cfg.v_local
    lo = store.layout
    bk = store.backing
    # Array-addressable backings (host/memmap) stage straight from a view;
    # the engine-backed file tier — and the sharded backing, which has no
    # whole-population array by design — reads its chunk through the block
    # API.  Checksummed backings also take the block API so every staged
    # byte is CRC-verified — a raw view would bypass torn-write detection.
    arr = (None if getattr(bk, "checksum", None) is not None
           else getattr(bk, "arr", None))
    disk = store.on_disk
    ww = lo.field_words(send) // v                 # ω in store words
    off_s, off_r = lo.offset(send), lo.offset(recv)
    procs = list(range(cfg.P)) if procs is None else list(procs)

    Ct = None
    if send_counts is not None and recv_counts is not None:
        Ct = store.field(send_counts).reshape(v, v).T.copy()
    fill_word = None
    if fill is not None:
        fill_word = _fill_word(fill, lo.field(send).dtype)

    # Host/memmap chunks are sliced as views; the engine-backed file tier's
    # read_block returns a *copy* the same size as the staging buffer, so a
    # chunk there holds 2x its column bytes resident (copy + blk).  The
    # in-place path slices views off the snapshot either way.
    chunk_copies = 1 if (arr is not None or send == recv) else 2
    alpha = _staging_alpha(cfg, chunk_copies * v * ww * WORD,
                           f"[v, ω] = [{v}, {ww * WORD}B] x{chunk_copies}")
    full = None
    if send == recv:
        # In-place shuffle: later chunks would read rows already
        # overwritten, so the whole field is snapshotted once and the
        # (still α-chunked) loop reads from the snapshot.  The snapshot
        # itself is v·v·ω staging — refuse when the cap cannot cover
        # snapshot + chunk rather than silently blowing the budget.
        full_bytes = v * v * ww * WORD
        if (cfg.device_cap_bytes is not None
                and full_bytes + alpha * v * ww * WORD
                > cfg.device_cap_bytes):
            raise ValueError(
                f"in-place tiered alltoallv (send == recv) must snapshot "
                f"the whole field ({full_bytes:,} B) on top of the "
                f"{alpha * v * ww * WORD:,} B chunk, exceeding "
                f"device_cap_bytes={cfg.device_cap_bytes:,}; use distinct "
                "send/recv fields or raise the cap"
            )
        full = bk.read_block(0, v, cols=slice(off_s, off_s + v * ww))
        if disk:
            self._account_disk(0, v, v * ww * WORD, write=False)

    for p in procs:
        stats = self.shard_stats[p]
        # One span per destination process's network phase, one per α-chunk
        # inside it (Alg 7.1.3 made visible): the trace shows exactly which
        # chunk of which shard's delivery the run spent its time in.
        with self.tracer.span(f"alltoallv.p{p}", tid="collective",
                              cat="collective", alpha=alpha):
            _alltoallv_proc_chunks(
                self, p, m, v, ww, alpha, arr, full, disk, off_s, off_r,
                fill, fill_word, Ct, bk, stats, chunk_copies)
    if Ct is not None:
        ct = Ct.astype(lo.field(recv_counts).dtype)
        for p in procs:
            store.with_field_rows(recv_counts, p * m, ct[p * m:(p + 1) * m])
    return store


def _staging_alpha(cfg, per_dst: int, what: str) -> int:
    """Destinations a host-staged chunk holds: ``alpha`` (every local
    context when unset), clamped so ``per_dst`` staging bytes each fit
    ``device_cap_bytes``; a cap below one destination raises."""
    alpha = cfg.v_local if cfg.alpha is None else cfg.alpha
    if cfg.device_cap_bytes is None:
        return alpha
    if per_dst > cfg.device_cap_bytes:
        raise ValueError(
            f"alltoallv staging needs {per_dst:,} bytes per destination "
            f"({what}) but device_cap_bytes={cfg.device_cap_bytes:,}; "
            "raise the cap")
    return min(alpha, cfg.device_cap_bytes // per_dst)


def _alltoallv_proc_chunks(self, p, m, v, ww, alpha, arr, full, disk,
                           off_s, off_r, fill, fill_word, Ct, bk, stats,
                           chunk_copies):
    """The α-chunk loop of :func:`_alltoallv_host` for one destination
    process ``p`` — split out so each chunk can carry its own trace span
    without deepening the host loop."""
    for c0 in range(p * m, (p + 1) * m, alpha):
        with self.tracer.span("chunk", tid="collective", cat="collective",
                              dst=p, c0=c0):
            c1 = min(c0 + alpha, (p + 1) * m)
            if full is not None:
                cols = full[:, c0 * ww:c1 * ww]
            elif arr is not None:
                cols = arr[:, off_s + c0 * ww:off_s + c1 * ww]
            else:
                cols = bk.read_block(
                    0, v, cols=slice(off_s + c0 * ww, off_s + c1 * ww))
            blk = _np.empty((c1 - c0, v, ww), _np.uint32)  # staging buffer
            blk[...] = _np.swapaxes(cols.reshape(v, c1 - c0, ww), 0, 1)
            if disk and full is None:
                # The chunk reads (c1-c0)·ω columns of every source row —
                # split across the source shards' ledgers.
                self._account_disk(0, v, (c1 - c0) * ww * WORD, write=False)
            stats.peak_stage_bytes = max(
                stats.peak_stage_bytes,
                chunk_copies * blk.nbytes
                + (full.nbytes if full is not None else 0),
            )
            if fill is not None:
                lane = _np.arange(ww)[None, None, :]
                _np.copyto(blk, fill_word,
                           where=lane >= Ct[c0:c1, :, None].astype(_np.int64))
            bk.write_block(c0, c1, blk.reshape(c1 - c0, v * ww),
                           cols=slice(off_r, off_r + v * ww))
            if disk:
                # The writes land entirely in destination shard p.
                self._account_disk(c0, c1, v * ww * WORD, write=True)


def _alltoallv_packed(self, store, send, recv, send_offsets, recv_offsets,
                      mode, fill, use_kernel, procs):
    """The packed form of :func:`alltoallv` (``offsets=``: 1-D message
    fields with ``[v + 1]`` offset fields); arguments as there."""
    cfg = self.cfg
    lo = store.layout
    fs, fr = lo.field(send), lo.field(recv)
    if len(fr.shape) != 1 or fr.dtype != fs.dtype:
        raise ValueError(
            f"a packed alltoallv needs 1-D send/recv fields of one dtype; "
            f"got {fs.shape} {fs.dtype.name} and {fr.shape} {fr.dtype.name}")
    for name in (send_offsets, recv_offsets):
        if name is None or lo.field(name).shape != (cfg.v + 1,):
            raise ValueError(
                f"a packed alltoallv needs [v + 1] offset fields, got "
                f"{name!r}")
    if fill is not None:
        from repro.kernels.alltoallv_deliver import check_fill_range
        check_fill_range(fill, fs.dtype)
    fill_word = 0 if fill is None else int(_fill_word(fill, fs.dtype))
    if isinstance(store, TieredStore):
        store = _alltoallv_packed_host(self, store, send, recv, send_offsets,
                                       recv_offsets, procs)
    else:
        with jax.named_scope("pems.alltoallv"):
            store = _alltoallv_packed_device(
                self, store, send, recv, send_offsets, recv_offsets,
                mode, fill_word, use_kernel)
    # The thesis' event counts, with the receive field's share per source
    # as the message bound ω.
    _ledger_alltoallv(self, fr.words * WORD // cfg.v, mode)
    return store


def _alltoallv_packed_device(self, store, send, recv, send_offsets,
                             recv_offsets, mode, fill_word, use_kernel):
    """Device tiers: each process stages its contexts' messages as
    transient tiles, delivers them (the kernel at ``P == 1``; assembled,
    then shipped by ``all_to_all`` over the mesh), and packs what its
    contexts received into their ``recv`` words.  ``mode="indirect"``
    holds the sent words behind a barrier (the PEMS1 indirect area) and
    takes the dense route, as ``use_kernel=False`` does."""
    from repro.kernels.alltoallv_deliver import (deliver_packed, pack_slices,
                                                 stage_slices)

    cfg = self.cfg
    lo = store.layout
    v, m = cfg.v, cfg.v_local
    n_s, size = lo.field_words(send), lo.field_words(recv)
    width = min(n_s, size)              # bounds every message that can land
    off_s, off_so = lo.offset(send), lo.offset(send_offsets)
    off_r, off_ro = lo.offset(recv), lo.offset(recv_offsets)
    kernel = use_kernel and mode == "direct"

    def exchange(local):                       # [m, words]: this proc's rows
        offs = _from_words(
            lax.slice(local, (0, off_so), (m, off_so + v + 1)), jnp.int32)
        words = lax.slice(local, (0, off_s), (m, off_s + n_s))
        if mode == "indirect":
            words = lax.optimization_barrier(words)
        if cfg.P == 1:
            packed, roff = deliver_packed(words, offs, size, fill=fill_word,
                                          use_kernel=kernel)
        else:
            counts = offs[:, 1:] - offs[:, :-1]             # [m(src), v(dst)]
            tiles = stage_slices(words, offs, width)
            out, got = _ship_packed_mesh(self, tiles, counts, fill_word,
                                         kernel)
            packed, roff = pack_slices(out, got, size, jnp.uint32(fill_word))
        local = lax.dynamic_update_slice(local, packed, (0, off_r))
        return lax.dynamic_update_slice(local, _to_words(roff), (0, off_ro))

    if cfg.P == 1:
        return ContextStore(lo, exchange(store.data))
    # check_vma=False: the staging and packing loops start their carries
    # from constants, which vary over the vp axis only once written.
    data = jax.shard_map(
        exchange,
        mesh=self.mesh,
        in_specs=(P(cfg.vp_axis, None),),
        out_specs=P(cfg.vp_axis, None),
        check_vma=False,
    )(store.data)
    return ContextStore(lo, data)


def _ship_packed_mesh(self, tiles, counts, fill_word, kernel):
    """The network phase of the packed delivery over the ``P > 1`` mesh:
    this process's staged tiles ``[m(src), v(dst), width]`` are assembled
    in destination order (mask fused) and shipped by ``all_to_all``,
    α-chunked as in :func:`_alltoallv_fused_mesh`.  Returns the tiles its
    contexts received ``[m(dst), v(src), width]`` and their counts
    ``[m(dst), v(src)]``."""
    cfg = self.cfg
    Pn, m, k = cfg.P, cfg.v_local, cfg.k
    width = tiles.shape[-1]
    x = tiles.reshape(m, Pn, m, width)
    c = counts.reshape(m, Pn, m)

    def ship(xc, cc):                          # [s, P, d, w], [s, P, d]
        return _ship_chunk(cfg, xc, cc, cc, fill_word, use_kernel=kernel)

    if cfg.alpha is None:
        y, yc = ship(x, c)
    else:
        rows = []
        for c0 in range(0, m, cfg.alpha):      # destination α-chunks
            c1 = min(c0 + cfg.alpha, m)
            parts = [ship(x[s0:s0 + k, :, c0:c1], c[s0:s0 + k, :, c0:c1])
                     for s0 in range(0, m, k)]   # source rounds of k
            rows.append((jnp.concatenate([p[0] for p in parts], axis=2),
                         jnp.concatenate([p[1] for p in parts], axis=2)))
        y = jnp.concatenate([r[0] for r in rows], axis=0)
        yc = jnp.concatenate([r[1] for r in rows], axis=0)
    return y.reshape(m, Pn * m, width), yc.reshape(m, Pn * m)


def _alltoallv_packed_host(self, store, send, recv, send_offsets,
                           recv_offsets, procs):
    """Backing tiers: per destination process, then by α-chunks of its
    contexts, stage each destination's incoming messages read straight
    from the senders' rows (the live words of each message, billed to the
    sender's shard) and write the destination's live words (billed to its
    shard), then its offsets.  Nothing past a receiver's total is read or
    written.  ``device_cap_bytes`` bounds the staging per chunk as in
    :func:`_alltoallv_host`."""
    cfg = self.cfg
    v, m = cfg.v, cfg.v_local
    lo = store.layout
    bk = store.backing
    arr = (None if getattr(bk, "checksum", None) is not None
           else getattr(bk, "arr", None))
    disk = store.on_disk
    size = lo.field_words(recv)
    off_s, off_r = lo.offset(send), lo.offset(recv)
    procs = list(range(cfg.P)) if procs is None else list(procs)

    offs = store.field(send_offsets).astype(_np.int64)      # [v(src), v+1]
    counts = offs[:, 1:] - offs[:, :-1]                     # [src, dst]
    roff = _np.zeros((v, v + 1), _np.int64)
    roff[:, 1:] = _np.cumsum(counts.T, axis=1)              # [dst, v+1]
    live = _np.minimum(roff[:, v], size)                    # words stored

    # A block read (file tier) returns a copy beside the staging row.
    chunk_copies = 1 if arr is not None else 2
    alpha = _staging_alpha(cfg, chunk_copies * size * WORD,
                           f"a {size * WORD:,} B receive field "
                           f"x{chunk_copies}")

    for p in procs:
        stats = self.shard_stats[p]
        with self.tracer.span(f"alltoallv.p{p}", tid="collective",
                              cat="collective", alpha=alpha):
            for c0 in range(p * m, (p + 1) * m, alpha):
                c1 = min(c0 + alpha, (p + 1) * m)
                nbytes = int(live[c0:c1].sum()) * WORD
                with self.tracer.span("chunk", tid="collective",
                                      cat="collective", dst=p, c0=c0,
                                      bytes=nbytes):
                    blk = _np.empty((c1 - c0, size), _np.uint32)
                    for d in range(c0, c1):
                        _stage_packed_row(self, blk[d - c0], d, offs, arr,
                                          bk, off_s, disk)
                        if live[d]:
                            bk.write_block(
                                d, d + 1, blk[d - c0:d - c0 + 1, :live[d]],
                                cols=slice(off_r, off_r + int(live[d])))
                            if disk:
                                self._account_disk(d, d + 1,
                                                   int(live[d]) * WORD,
                                                   write=True)
                    stats.peak_stage_bytes = max(stats.peak_stage_bytes,
                                                 chunk_copies * blk.nbytes)
    ro = roff.astype(lo.field(recv_offsets).dtype)
    for p in procs:
        store.with_field_rows(recv_offsets, p * m, ro[p * m:(p + 1) * m])
    return store


def _stage_packed_row(self, row, d, offs, arr, bk, off_s, disk):
    """Fill ``row`` with destination ``d``'s incoming messages end to end,
    reading each sender's slice for ``d`` (cut where ``row`` ends)."""
    pos = 0
    for s in range(offs.shape[0]):
        a = int(offs[s, d])
        n = min(int(offs[s, d + 1]) - a, row.size - pos)
        if n <= 0:
            continue
        if arr is not None:
            row[pos:pos + n] = arr[s, off_s + a:off_s + a + n]
        else:
            row[pos:pos + n] = bk.read_block(
                s, s + 1, cols=slice(off_s + a, off_s + a + n))[0]
        if disk:
            self._account_disk(s, s + 1, n * WORD, write=False)
        pos += n


def _global_transpose(self, M: jnp.ndarray) -> jnp.ndarray:
    """[v(src), v(dst), w] → [v(dst), v(src), w], sharded on axis 0 over the
    vp axis when P > 1 (α-chunked all_to_all, Alg 7.1.3)."""
    cfg = self.cfg
    if cfg.P == 1:
        return jnp.swapaxes(M, 0, 1)

    m = cfg.v_local
    Pn = cfg.P
    alpha = m if cfg.alpha is None else cfg.alpha
    w = M.shape[-1]

    def f(local):                              # [m(src_local), v, w]
        x = local.reshape(m, Pn, m, w)         # (src_local, dst_proc, dst_local, w)
        chunks = []
        for c0 in range(0, m, alpha):
            c1 = min(c0 + alpha, m)
            xc = x[:, :, c0:c1, :]             # bounded buffer: α·ω per lane
            yc = lax.all_to_all(
                xc, cfg.vp_axis, split_axis=1, concat_axis=0, tiled=False
            )                                   # [P(src_proc), m, c, w]
            chunks.append(yc)
        y = jnp.concatenate(chunks, axis=2) if len(chunks) > 1 else chunks[0]
        y = y.reshape(Pn * m, m, w)            # (src_global, dst_local, w)
        return jnp.swapaxes(y, 0, 1)           # (dst_local, src_global, w)

    return jax.shard_map(
        f,
        mesh=self.mesh,
        in_specs=(P(cfg.vp_axis, None, None),),
        out_specs=P(cfg.vp_axis, None, None),
    )(M)


def _ledger_alltoallv(self, omega_b: int, mode: str) -> None:
    cfg = self.cfg
    B = cfg.block_bytes
    v, k, Pn = cfg.v, cfg.k, cfg.P
    m = cfg.v_local
    mu = self.layout.live_bytes
    led = self.ledger

    if mode == "direct":
        # Alg 7.1.1 / 7.1.2 event counts (validated vs Lemma 7.1.3 and the
        # exact parallel model in analysis.pems2_alltoallv_par_io_exact).
        delta = (m * m + m * k) // 2           # ID-ordered rounds, per proc
        led.add_swap_out(v * max(mu - v * omega_b, 0), B)
        led.add_msg_direct(Pn * delta * omega_b, B)
        led.add_msg_indirect(Pn * 2 * (m * m - delta) * omega_b, B)
        if Pn > 1:
            led.add_network(v * (v - m) * omega_b)
            led.add_msg_direct(v * (v - m) * omega_b, B)
            # Network launches: one bulk all-to-all when unchunked, else one
            # per (source round of k, destination α-chunk) — Alg 7.1.3,
            # validated against analysis.pems2_alltoallv_par_network_rounds.
            if cfg.alpha is None:
                led.add_network_rounds(1)
            else:
                led.add_network_rounds((m // k) * -(-m // cfg.alpha))
        led.add_boundary(2 * v * v * B, B)
        led.add_barrier(3)
    else:
        # Alg 2.2.1 event counts (Lemma 2.2.1: 4vμ + 2v²ω) + §2.3.3 indirect
        # network routing (each remote message crosses the wire twice).
        led.add_msg_indirect(v * v * omega_b, B)      # write to indirect area
        led.add_swap_out(v * mu, B)
        led.add_swap_in(v * mu, B)
        led.add_msg_indirect(v * v * omega_b, B)      # read back for delivery
        led.add_swap_out(v * mu, B)
        led.add_swap_in(v * mu, B)
        if Pn > 1:
            led.add_network(2 * v * (v - m) * omega_b)
        led.require_disk(v * mu // Pn + v * v * omega_b)
        led.add_barrier(2)


# --------------------------------------------------------------------------- #
# Rooted collectives (§7.2–7.4) — global-array ops; GSPMD inserts the network  #
# collectives, the ledger carries the thesis' worst-case EM terms.             #
# --------------------------------------------------------------------------- #

def bcast(self, store: ContextStore, field: str, root: int = 0,
          procs=None) -> ContextStore:
    """EM-Bcast (Alg 7.2.1): root's field value lands in every context.

    On a tiered store ``procs`` restricts the write side to the listed
    processes' shards (the root row is read wherever it lives)."""
    cfg = self.cfg
    if procs is not None and not isinstance(store, TieredStore):
        raise ValueError("procs= requires a backing-tier store")
    if isinstance(store, TieredStore):
        # Read only the root context's field range off the backing store.
        m = cfg.v_local
        off = store.layout.offset(field)
        nw = store.layout.field_words(field)
        row = store.backing.read_block(root, root + 1,
                                       cols=slice(off, off + nw))
        if store.on_disk:
            self._account_disk(root, root + 1, row.nbytes, write=False)
        for p in (range(cfg.P) if procs is None else procs):
            store.backing.write_block(p * m, (p + 1) * m, row,  # every row
                                      cols=slice(off, off + nw))
            if store.on_disk:
                self._account_disk(p * m, (p + 1) * m, row.nbytes,
                                   write=True)
    elif cfg.P == 1:
        with jax.named_scope("pems.bcast"):
            vals = store.field(field)          # [v, ...]
            val = lax.index_in_dim(vals, root, axis=0, keepdims=False)
            store = store.with_field(field,
                                     jnp.broadcast_to(val, vals.shape))
    else:
        with jax.named_scope("pems.bcast"):
            store = _bcast_mesh(self, store, field, root)

    B = cfg.block_bytes
    mu = self.layout.live_bytes
    omega_b = self.layout.field_bytes(field)
    # Lemma 7.2.1: root-partition sharers swap out and back in; every VP
    # delivers ω to its context.
    self.ledger.add_swap_out(cfg.v * mu // (cfg.P * cfg.k), B)
    self.ledger.add_swap_in(cfg.v * mu // (cfg.P * cfg.k), B)
    self.ledger.add_msg_direct(cfg.v * omega_b, B)
    if cfg.P > 1:
        self.ledger.add_network((cfg.P - 1) * omega_b)
    self.ledger.add_barrier()
    return store


def _bcast_mesh(self, store: ContextStore, field: str, root: int
                ) -> ContextStore:
    """Device-tier Bcast over the ``vp`` mesh: the root's owner reads its
    field words locally, an ``all_gather`` of that one row crosses the
    network, and every process writes it into its own rows.  No slice ever
    indexes the sharded axis, so the store keeps its ``vp`` sharding."""
    cfg = self.cfg
    m = cfg.v_local
    off = store.layout.offset(field)
    nw = store.layout.field_words(field)

    def f(local):                              # [m, words]: this proc's rows
        row = lax.slice(local, (root % m, off), (root % m + 1, off + nw))
        rows = lax.all_gather(row, cfg.vp_axis)        # [P, 1, nw]
        val = rows[root // m]                          # [1, nw]
        return lax.dynamic_update_slice(
            local, jnp.broadcast_to(val, (m, nw)), (0, off))

    data = jax.shard_map(
        f,
        mesh=self.mesh,
        in_specs=(P(cfg.vp_axis, None),),
        out_specs=P(cfg.vp_axis, None),
    )(store.data)
    return ContextStore(store.layout, data)


def gather(self, store: ContextStore, send: str, recv: str, root: int = 0,
           procs=None) -> ContextStore:
    """EM-Gather (Alg 7.3.1): every VP's ``send`` ([ω]) lands in the root's
    ``recv`` ([v, ω]).  Non-root recv fields are left untouched.

    On a tiered store ``procs`` restricts the write side: the root row is
    only written when its shard (``root // (v/P)``) is listed."""
    cfg = self.cfg
    fs = store.layout.field(send)
    fr = store.layout.field(recv)
    if fr.shape != (cfg.v,) + fs.shape:
        raise ValueError(f"recv must be [v, *send.shape]; got {fr.shape}")
    if procs is not None and not isinstance(store, TieredStore):
        raise ValueError("procs= requires a backing-tier store")
    if isinstance(store, TieredStore):
        A = store.field(send)                  # host copy [v, ...]
        w = _np.ascontiguousarray(A.astype(_np.dtype(fr.dtype))).reshape(-1)
        off = store.layout.offset(recv)
        # Only the root context's recv range is touched on the backing store.
        if procs is None or root // cfg.v_local in procs:
            store.backing.write_block(root, root + 1,
                                      w.view(_np.uint32)[None],
                                      cols=slice(off, off + w.size))
            if store.on_disk:
                self._account_disk(root, root + 1, w.nbytes, write=True)
    else:
        with jax.named_scope("pems.gather"):
            A = store.field(send)              # [v, ...] gathered result
            R = store.field(recv)              # [v, v, ...]
            R = R.at[root].set(A.astype(fr.dtype))
            store = store.with_field(recv, R)

    B = cfg.block_bytes
    omega_b = self.layout.field_bytes(send)
    # Lemma 7.3.1 (exact form): the root may swap out (μ) and the gathered
    # v·ω result is written to its context on disk.
    self.ledger.add_swap_out(self.layout.live_bytes, B)
    self.ledger.add_msg_direct(cfg.v * omega_b, B)
    if cfg.P > 1:
        self.ledger.add_network((cfg.v - cfg.v_local) * omega_b)
    self.ledger.add_barrier()
    return store


def allgather(self, store: ContextStore, send: str, recv: str,
              procs=None) -> ContextStore:
    """Every VP receives every VP's ``send`` into ``recv`` ([v, ω]).

    On a tiered store ``procs`` restricts the write side to the listed
    processes' shards (sources are read from every shard)."""
    cfg = self.cfg
    if procs is not None and not isinstance(store, TieredStore):
        raise ValueError("procs= requires a backing-tier store")
    if isinstance(store, TieredStore):
        # Stage only the gathered [v, ω] row (every receiver gets the same
        # bytes) and write it per destination shard — never the dense
        # [v, v·ω] broadcast the tier cannot afford.
        m = cfg.v_local
        A = store.field(send)                  # host copy [v, ...]
        w = _np.ascontiguousarray(
            A.astype(_np.dtype(store.layout.field(recv).dtype))).reshape(-1)
        off = store.layout.offset(recv)
        for p in (range(cfg.P) if procs is None else procs):
            store.backing.write_block(p * m, (p + 1) * m,
                                      w.view(_np.uint32)[None],
                                      cols=slice(off, off + w.size))
            if store.on_disk:
                self._account_disk(p * m, (p + 1) * m, w.nbytes, write=True)
            st = self.shard_stats[p]
            st.peak_stage_bytes = max(st.peak_stage_bytes, w.nbytes)
    else:
        A = store.field(send)                  # [v, ...]
        out = jnp.broadcast_to(
            A[None], (cfg.v,) + A.shape
        ).astype(store.layout.field(recv).dtype)
        store = store.with_field(recv, out)
    # An allgather is an Alltoallv with equal messages — same ledger shape.
    _ledger_alltoallv(self, self.layout.field_bytes(send), "direct")
    return store


def reduce(self, store: ContextStore, field: str, out_field: str,
           op: str = "add", root: int = 0, procs=None) -> ContextStore:
    """EM-Reduce (Alg 7.4.1): vectorised reduction of each VP's ``field``
    ([n]) into the root's ``out_field`` ([n]).

    On a tiered store ``procs`` gates the root write like :func:`gather`."""
    if procs is not None and not isinstance(store, TieredStore):
        raise ValueError("procs= requires a backing-tier store")
    if isinstance(store, TieredStore):
        red = _tiered_reduce(self, store, field, op)
        fr = store.layout.field(out_field)
        w = _np.ascontiguousarray(
            red.astype(_np.dtype(fr.dtype))).reshape(-1)
        off = store.layout.offset(out_field)
        if procs is None or root // self.cfg.v_local in procs:
            store.backing.write_block(root, root + 1,
                                      w.view(_np.uint32)[None],
                                      cols=slice(off, off + w.size))
            if store.on_disk:
                self._account_disk(root, root + 1, w.nbytes, write=True)
    else:
        vals = store.field(field)              # [v, n]
        red = _reduce_op(op)(vals)
        R = store.field(out_field)
        R = R.at[root].set(red.astype(R.dtype))
        store = store.with_field(out_field, R)
    _ledger_reduce(self, self.layout.field_bytes(out_field))
    return store


def allreduce(self, store: ContextStore, field: str, out_field: str,
              op: str = "add", procs=None) -> ContextStore:
    if procs is not None and not isinstance(store, TieredStore):
        raise ValueError("procs= requires a backing-tier store")
    if isinstance(store, TieredStore):
        m = self.cfg.v_local
        red = _tiered_reduce(self, store, field, op)
        out = _np.broadcast_to(red[None], (m,) + red.shape).astype(
            _np.dtype(store.layout.field(out_field).dtype))
        for p in (range(self.cfg.P) if procs is None else procs):
            store.with_field_rows(out_field, p * m, out)
    else:
        vals = store.field(field)
        red = _reduce_op(op)(vals)
        out = jnp.broadcast_to(red[None], vals.shape)
        store = store.with_field(
            out_field, out.astype(store.layout.field(out_field).dtype)
        )
    _ledger_reduce(self, self.layout.field_bytes(out_field))
    # The rebroadcast delivers n·ω to every context.
    self.ledger.add_msg_direct(
        (self.cfg.v - 1) * self.layout.field_bytes(out_field),
        self.cfg.block_bytes,
    )
    return store


def _tiered_reduce(self, store, field: str, op: str) -> _np.ndarray:
    """Reduce a backing-tier field.  The reduction itself runs on device
    (same jnp op, same accumulation order) so the result is bit-identical to
    the device tier even for float32 fields; the field matrix [v, n] is
    assumed to fit the device budget (reduce operands are collective-sized,
    not data-sized)."""
    vals = store.field(field)
    red = _np.asarray(_reduce_op(op)(jax.device_put(vals)))
    self.ledger.add_tier_in(vals.nbytes, disk=False)
    self.ledger.add_tier_out(red.nbytes, disk=False)
    return red


def _reduce_op(op: str):
    ops = {
        "add": lambda x: jnp.sum(x, axis=0),
        "max": lambda x: jnp.max(x, axis=0),
        "min": lambda x: jnp.min(x, axis=0),
    }
    if op not in ops:
        raise ValueError(f"unsupported reduce op {op!r} (PEMS requires "
                         "commutative+associative operators, §7.4)")
    return ops[op]


def _ledger_reduce(self, n_bytes: int) -> None:
    cfg = self.cfg
    # Lemma 7.4.2: the root delivers the n-vector result to its context; the
    # network phase is a logarithmic tree (Lemma 7.4.3).
    self.ledger.add_msg_direct(n_bytes, cfg.block_bytes)
    if cfg.P > 1:
        import math
        self.ledger.add_network(n_bytes * math.ceil(math.log2(cfg.P)))
    self.ledger.add_barrier(2)
