"""The PEMS2 superstep executor.

Simulates ``v`` virtual processors on ``P`` real processors (mesh devices)
with ``k`` concurrently-resident contexts per real processor, exactly the
thesis' model (§3.2): execution proceeds in deterministic ID-ordered rounds of
``P·k`` virtual processors (§6.5 — this ordering is what guarantees full disk
parallelism and fixes the direct-delivery count δ).

Drivers (§5):
  * ``explicit`` — every round swaps the full *live* context in and out
    (PEMS2 swaps only allocated bytes, §6.6).
  * ``sliced``   — the superstep declares which fields it reads/writes; only
    those bytes move.  This is the memory-mapped driver of §5.2 made exact:
    JAX traces are static, so "which pages get touched" is known, not guessed.
  * ``async``    — double-buffered rounds: the next round's swap-in is issued
    before the current round's compute completes so XLA can overlap the copy
    with compute (the STXXL-file driver of §5.1).

All drivers produce bit-identical results; they differ in bytes moved (the
ledger) and in schedule (wall-clock benchmarks).

Backing tiers (``repro.core.backing``): with ``tier="host"``, ``"memmap"``
or ``"file"`` the full ``[v, words]`` population lives off-device (host RAM,
an ``np.memmap`` file, or a file behind the :mod:`repro.io` engine) and the
round loop becomes a *host-driven* pipeline: each round's ``k`` contexts —
live allocator bytes only (§6.6) — are ``jax.device_put`` onto the device,
computed, and written back.  Under the ``async`` driver a prefetch thread
issues round ``r+1``'s swap-in while round ``r`` computes, so the disk/PCIe
transfer genuinely overlaps compute (the STXXL-file driver, §5.1) rather
than merely reordering on-device copies; on the ``file`` tier the writeback
is additionally left in flight on the engine's submission queue, so round
``r-1``'s swap-out and round ``r+1``'s swap-in overlap round ``r``'s compute
in *both* directions (visible in ``TierStats.rw_overlap_events``).  The
ledger records the measured per-tier traffic alongside the modeled counters,
and ``Pems.tier_stats`` the wall-clock overlap.
"""

from __future__ import annotations

import dataclasses
import time
import weakref
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.io import IO_DRIVERS
from repro.obs import NOOP, Tracer, trace_events, write_trace

from .backing import TIERS, TieredStore, make_backing
from .context import (
    Ctx,
    ContextLayout,
    ContextStore,
    field_word_index,
    init_store,
    row_major,
)
from .iostats import IOLedger, TierStats

DRIVERS = ("explicit", "sliced", "async")

# Jitted tiered round bodies of every executor: stage function -> {(layout,
# k) -> body} (see Pems._tiered_body).
_TIERED_BODIES = weakref.WeakKeyDictionary()


def _layout_key(lo: ContextLayout) -> tuple:
    """What a round body's trace reads of a layout: each field's name,
    offset, shape and dtype, and the context's words."""
    return (lo.words,) + tuple((n, lo.offset(n), lo.field(n).shape,
                                lo.field(n).dtype.name) for n in lo.names)


@dataclasses.dataclass
class PemsConfig:
    """Simulation parameters (thesis Appendix B.3).

    Every knob is documented at length in ``docs/TUNING.md``; the short
    version:

    * ``v``/``k``/``P`` — total virtual processors, concurrently-resident
      contexts per real processor, and real processors.  ``v`` must divide
      by ``P`` and ``v/P`` by ``k``; each real processor simulates its
      ``v/P`` contexts in ``v/(P·k)`` ID-ordered rounds (§6.5).
    * ``driver`` — round swap strategy: ``explicit`` (full live context),
      ``sliced`` (declared fields only), ``async`` (double-buffered
      prefetch, §5.1).  Bit-identical results; different bytes/schedule.
    * ``tier`` — where the ``[v, words]`` population lives: ``device``
      (resident, whole-program jit), ``host`` (RAM), ``memmap`` (disk via
      ``np.memmap``), ``file`` (disk via the :mod:`repro.io` engine).  With
      ``P > 1`` on a non-device tier the backing is **sharded**: each
      process owns rows ``[p·v/P, (p+1)·v/P)`` in its own backing file
      (``backing_path + ".shard<p>"``) with its own engine and its own
      ``pems.shard_ledgers[p]``/``shard_stats[p]`` accounting — the full
      parallel disk model (§6.3), no mesh required.
    * ``alpha`` — Alltoallv chunk: how many destination contexts are staged
      or shipped at once (Alg 7.1.3), ``1 <= alpha <= v/P`` or ``None`` for
      unchunked.  Bounds the staging buffer per Lemma 7.1.9.
    * ``block_bytes`` — B, the *modeled* ledger block size (bytes).
    * ``device_cap_bytes`` — device-memory budget (bytes) for resident
      contexts + collective staging; construction fails if the config
      cannot fit, and tiered collectives clamp their chunks under it.
    * ``backing_path`` — disk tiers: backing file location (created
      sparse at ``v·μ`` bytes; existing contents are reused, never zeroed).
    * ``io_driver``/``io_queue_depth``/``io_retries``/``io_backoff_s`` —
      file tier only: positional-I/O driver (``buffered``/``odirect``/
      ``mmap``, or ``"faulty:<inner>"`` to inject faults), bounded
      in-flight requests, transient-error retries per request, and base
      backoff seconds (doubles per retry).
    * ``fault_spec`` — what the faulty driver injects (grammar in
      :mod:`repro.io.faults`).  A ``shard=N`` clause (requires
      ``0 <= N < P``) targets one shard's driver only — the
      single-disk-failure model.
    * ``checksums`` — disk tiers: per-64KiB-segment CRC sidecars on the
      backing, verified on every read (torn-write detection).
    * ``merge_kernel``/``merge_tile`` — app-level merge stages (PSRS):
      route the merge through the tiled k-way merge kernel
      (:mod:`repro.kernels.kway_merge`) in ``merge_tile``-wide output
      tiles, instead of the dense ``jnp.sort`` re-sort of the received
      buckets.  Bit-identical either way; ``merge_tile`` must be a power
      of two.
    * ``trace``/``trace_path`` — :mod:`repro.obs` span tracing: record
      superstep/round/engine/collective/recovery spans into per-process
      ring buffers (results stay bit-identical; hot paths pay one
      attribute check when off).  ``trace_path`` is where
      :meth:`Pems.export_trace` writes the merged Perfetto JSON.

    Raises ``ValueError`` at construction for any invalid combination —
    unknown names, out-of-range ``alpha``, ``io_*`` knobs without
    ``tier="file"``, ``fault_spec`` without a faulty driver or targeting a
    shard ``>= P``, ``checksums`` on a non-disk tier, or indivisible
    ``v``/``P``/``k``.
    """

    v: int                      # total virtual processors
    k: int = 1                  # concurrently-resident contexts per real proc
    P: int = 1                  # real processors (mesh axis size)
    block_bytes: int = 4096     # B — ledger block size
    driver: str = "explicit"
    alpha: Optional[int] = None  # Alltoallv network chunk (messages at once)
    vp_axis: str = "vp"
    tier: str = "device"        # backing tier: device | host | memmap | file
    backing_path: Optional[str] = None   # disk tiers: backing file location
    device_cap_bytes: Optional[int] = None  # device-memory budget for contexts
    io_driver: Optional[str] = None  # file tier: buffered | odirect | mmap
                                     # (or "faulty:<driver>" for injection)
    io_queue_depth: int = 8     # file tier: bounded in-flight engine requests
    io_retries: int = 2         # file tier: transient-error retries/request
    io_backoff_s: float = 0.002  # file tier: base retry backoff (doubles)
    fault_spec: Optional[str] = None  # faulty driver: what to inject
                                      # (see repro.io.faults grammar)
    checksums: bool = False     # disk tiers: per-block CRC sidecar on the
                                # backing file, verified on every read
    merge_kernel: bool = True   # app merge stages: tiled k-way merge kernel
                                # (False = dense jnp.sort re-sort, the seed
                                # path; bit-identical either way)
    merge_tile: int = 256       # k-way merge output tile width (power of
                                # two; one merge grid step per tile)
    trace: bool = False         # repro.obs span tracing (per-process ring
                                # buffers; bit-identical results either way)
    trace_path: Optional[str] = None  # where export_trace() writes the
                                      # merged Perfetto JSON (requires trace)

    def __post_init__(self):
        if self.driver not in DRIVERS:
            raise ValueError(f"unknown driver {self.driver!r}")
        if self.tier not in TIERS:
            raise ValueError(f"unknown tier {self.tier!r} (choose from {TIERS})")
        # The repro.io knobs fail here, at construction, like every other
        # config field — not deep inside make_backing at run time.
        if self.tier == "file":
            if self.io_driver is None:
                self.io_driver = "buffered"
            parts = self.io_driver.split(":")
            base, wrappers = parts[-1], parts[:-1]
            if base not in IO_DRIVERS or not all(
                    w in ("faulty", "sanitize") for w in wrappers):
                raise ValueError(
                    f"unknown io_driver {self.io_driver!r} "
                    f"(choose from {IO_DRIVERS}, optionally wrapped as "
                    "'faulty:<driver>' / 'sanitize:<driver>')"
                )
        elif self.io_driver is not None:
            raise ValueError(
                f"io_driver={self.io_driver!r} requires tier='file' "
                f"(got tier={self.tier!r})"
            )
        if self.fault_spec is not None:
            if "faulty" not in (self.io_driver or "").split(":")[:-1]:
                raise ValueError(
                    "fault_spec requires io_driver='faulty:<driver>' on "
                    f"tier='file' (got io_driver={self.io_driver!r}, "
                    f"tier={self.tier!r})"
                )
            from repro.io.faults import FaultSpec, split_shard_clause
            shard, rest = split_shard_clause(self.fault_spec)
            if shard is not None and shard >= self.P:
                raise ValueError(
                    f"fault_spec targets shard {shard} but P={self.P} "
                    f"(shard indices are 0..P-1)"
                )
            FaultSpec.parse(rest)   # syntax errors fail here
        if self.checksums and self.tier not in ("memmap", "file"):
            raise ValueError(
                f"checksums=True requires a disk tier ('memmap' or 'file'), "
                f"got tier={self.tier!r}"
            )
        if self.io_retries != int(self.io_retries) or self.io_retries < 0:
            raise ValueError(
                f"io_retries={self.io_retries!r} must be an integer >= 0")
        self.io_retries = int(self.io_retries)
        if self.io_backoff_s < 0:
            raise ValueError(
                f"io_backoff_s={self.io_backoff_s!r} must be >= 0")
        if (self.io_queue_depth != int(self.io_queue_depth)
                or self.io_queue_depth < 1):
            raise ValueError(
                f"io_queue_depth={self.io_queue_depth!r} must be an "
                "integer >= 1"
            )
        self.io_queue_depth = int(self.io_queue_depth)
        if (self.merge_tile != int(self.merge_tile) or self.merge_tile < 2
                or int(self.merge_tile) & (int(self.merge_tile) - 1)):
            raise ValueError(
                f"merge_tile={self.merge_tile!r} must be a power-of-two "
                "integer >= 2 (one k-way merge grid step per tile)"
            )
        self.merge_tile = int(self.merge_tile)
        if self.trace_path is not None and not self.trace:
            raise ValueError(
                f"trace_path={self.trace_path!r} requires trace=True "
                "(nothing records spans to export otherwise)"
            )
        if self.v % self.P:
            raise ValueError("v must be divisible by P")
        if (self.v // self.P) % self.k:
            raise ValueError("v/P must be divisible by k")
        if self.alpha is not None:
            # The Alltoallv network chunk (Alg 7.1.3).  alpha=0 used to fall
            # through as "unchunked" (`alpha or m`), and out-of-range values
            # passed straight into the chunk loop; validate here so every
            # consumer (mesh network phase, tiered staging, ledger rounds)
            # sees a sane value.
            if self.alpha != int(self.alpha):
                raise ValueError(
                    f"alpha={self.alpha!r} must be an integer chunk size"
                )
            self.alpha = int(self.alpha)
            if not 1 <= self.alpha <= self.v_local:
                raise ValueError(
                    f"alpha={self.alpha} out of range: the Alltoallv "
                    f"network chunk must satisfy 1 <= alpha <= v/P = "
                    f"{self.v_local} (alpha=None means unchunked, one "
                    "chunk of v/P destinations)"
                )
    @property
    def v_local(self) -> int:
        return self.v // self.P

    @property
    def rounds(self) -> int:
        return self.v_local // self.k


class Pems:
    """Executor: superstep engine + I/O ledger.  Collective methods are bound
    from :mod:`repro.core.collectives`."""

    def __init__(self, cfg: PemsConfig, layout: ContextLayout,
                 mesh: Optional[Mesh] = None):
        self.cfg = cfg
        self.layout = layout
        self.mesh = mesh
        self.ledger = IOLedger()
        self.tier_stats = TierStats()
        # Per-process accounting (the parallel disk model, §6.3).  At
        # P == 1 the shard lists alias the main ledger/stats, so existing
        # single-process call sites see identical numbers either way; at
        # P > 1 each shard's backing bills its own entry and
        # merged_shard_ledger() recovers the P == 1 totals.
        if cfg.P == 1 or cfg.tier == "device":
            self.shard_ledgers = [self.ledger]
            self.shard_stats = [self.tier_stats]
        else:
            self.shard_ledgers = [IOLedger() for _ in range(cfg.P)]
            self.shard_stats = [TierStats() for _ in range(cfg.P)]
        self.backing = None   # last backing this executor created (tiered)
        self.cursors = None   # optional per-process durable SuperstepCursors:
                              # when set, _run_tiered notes round progress
        # repro.obs tracing: the main tracer (stage/superstep/collective
        # lanes, pid 0 on export) plus one tracer per process for the round
        # loop and its shard's engine (pid p+1) — all on one shared epoch so
        # the merged trace has comparable timestamps.  Disabled, everything
        # aliases the NOOP singleton: instrumented code pays one attribute
        # check, and results are bit-identical either way.
        if cfg.trace:
            self.tracer = Tracer(name="main")
            if cfg.tier == "device":
                self.shard_tracers = [self.tracer]
            else:
                self.shard_tracers = [
                    Tracer(epoch=self.tracer.epoch, name=f"shard{p}")
                    for p in range(cfg.P)
                ]
        else:
            self.tracer = NOOP
            self.shard_tracers = [NOOP] * max(1, cfg.P)
        if cfg.P > 1 and cfg.tier == "device" and mesh is None:
            raise ValueError("P > 1 requires a mesh with the vp axis "
                             "(device tier; backing tiers shard instead)")
        if mesh is not None and mesh.shape[cfg.vp_axis] != cfg.P:
            raise ValueError(
                f"mesh axis {cfg.vp_axis}={mesh.shape[cfg.vp_axis]} != P={cfg.P}"
            )
        if cfg.device_cap_bytes is not None:
            # Device-memory budget for contexts: the device tier must fit the
            # whole population; a backing tier needs its in-flight round
            # blocks — input + output, plus the prefetched next block under
            # the double-buffered async driver.
            if cfg.tier == "device":
                need, what = cfg.v * layout.mu_bytes, "v·mu"
            else:
                bufs = 3 if cfg.driver == "async" else 2
                need = bufs * cfg.k * layout.mu_bytes
                what = f"{bufs}·k·mu in-flight round blocks"
            if need > cfg.device_cap_bytes:
                raise ValueError(
                    f"device-resident contexts need {need:,} bytes ({what}) "
                    f"but device_cap_bytes={cfg.device_cap_bytes:,}; "
                    "lower k or use tier='host'/'memmap'/'file'"
                )
        # PEMS2 disk requirement: exactly vμ/P per real processor (§6.3).
        self.ledger.require_disk(cfg.v * layout.mu_bytes // cfg.P)
        for led in self.shard_ledgers:
            led.require_disk(cfg.v * layout.mu_bytes // cfg.P)

    # ------------------------------------------------------ per-process views
    @property
    def cursor(self):
        """The single-process durable cursor (process 0's at ``P > 1``).
        Assigning a cursor here wraps it as a one-element ``cursors`` list —
        the pre-sharding call sites keep working unchanged."""
        return self.cursors[0] if self.cursors else None

    @cursor.setter
    def cursor(self, cur):
        self.cursors = None if cur is None else [cur]

    def merged_shard_ledger(self) -> IOLedger:
        """Sum of the per-shard ledgers — equals the ``P == 1`` ledger's
        measured counters for the same workload (the sharding invariant the
        tier-1 tests pin)."""
        out = IOLedger()
        for led in self.shard_ledgers:
            out = out.merge(led)
        return out

    def merged_shard_stats(self) -> TierStats:
        out = TierStats()
        for st in self.shard_stats:
            out = out.merge(st)
        return out

    # -------------------------------------------------------- observability
    def metrics_snapshot(self) -> dict:
        """Flat metric-name dict subsuming ``TierStats`` and ``IOLedger``:
        ``tier.*``/``ledger.*`` are the run totals (per-shard entries merged
        at ``P > 1``), ``shard<p>.tier.*`` the per-process breakdown.
        Embedded under ``"metrics"`` in exported traces, so the report CLI
        can cross-check span-derived numbers against the counters."""
        m = {}
        stats = (self.merged_shard_stats() if len(self.shard_stats) > 1
                 else self.tier_stats)
        m.update(stats.snapshot())
        led = self.ledger
        for sl in self.shard_ledgers:
            if sl is not led:
                led = led.merge(sl)
        m.update(led.snapshot())
        if len(self.shard_stats) > 1:
            for p, st in enumerate(self.shard_stats):
                m.update(st.snapshot(prefix=f"shard{p}.tier"))
        return m

    def export_trace(self, path: Optional[str] = None) -> str:
        """Write the recorded spans as one Perfetto-loadable JSON trace.

        The main tracer's events (pid 0) and, under a backing tier, each
        per-process tracer's (pid ``p+1``) are merged in memory, each
        keeping its own process lane, and written with the
        :meth:`metrics_snapshot` and the shared epoch's clock readings to
        ``path`` (default: the config's ``trace_path``).  Load the result
        in https://ui.perfetto.dev or summarize it with
        ``python -m repro.obs report <path>``."""
        path = self.cfg.trace_path if path is None else path
        if path is None:
            raise ValueError(
                "export_trace needs a path (argument or "
                "PemsConfig.trace_path)")
        if not self.cfg.trace:
            raise ValueError(
                "export_trace requires PemsConfig(trace=True) — nothing "
                "recorded spans")
        events = trace_events(self.tracer, pid=0, process_name="main")
        if self.shard_tracers[0] is not self.tracer:
            for p, tr in enumerate(self.shard_tracers):
                events += trace_events(tr, pid=p + 1, process_name=tr.name)
        events.sort(key=lambda e: (e["ph"] != "M", e.get("ts", 0.0)))
        return write_trace(path, events, metrics=self.metrics_snapshot(),
                           tracer=self.tracer)

    def _account_disk(self, r0: int, r1: int, row_bytes: int,
                      write: bool) -> None:
        """Bill measured disk traffic for global rows ``[r0, r1)`` to the
        owning shard ledger(s) — the single main ledger at ``P == 1``."""
        from .backing import shard_row_ranges
        if len(self.shard_ledgers) == 1:
            led = self.shard_ledgers[0]
            (led.add_disk_write if write
             else led.add_disk_read)((r1 - r0) * row_bytes)
            return
        m = self.cfg.v_local
        for p, a, b in shard_row_ranges(m, r0, r1):
            led = self.shard_ledgers[p]
            (led.add_disk_write if write
             else led.add_disk_read)((b - a) * row_bytes)

    # ------------------------------------------------------------------ setup
    def init(self, init_fn=None, tier: Optional[str] = None,
             backing_path: Optional[str] = None):
        """Create the context population.  ``tier`` (default: the config's)
        selects device residency or a host/disk backing store."""
        tier = self.cfg.tier if tier is None else tier
        if tier not in TIERS:
            # Validate the override as early as the config's own tier.
            raise ValueError(f"unknown tier {tier!r} (choose from {TIERS})")
        if tier != "device":
            return self._init_tiered(init_fn, tier,
                                     backing_path or self.cfg.backing_path)
        if self.mesh is None:
            return init_store(self.layout, self.cfg.v, init_fn)
        # Built straight into its vp shards: no device ever holds the whole
        # population (at P = 4 it is up to four times one device's memory).
        data = jax.jit(
            lambda: init_store(self.layout, self.cfg.v, init_fn).data,
            out_shardings=NamedSharding(self.mesh, P(self.cfg.vp_axis, None)),
        )()
        return ContextStore(self.layout, data)

    def _init_tiered(self, init_fn, tier: str,
                     backing_path: Optional[str]) -> TieredStore:
        cfg, lo = self.cfg, self.layout
        backing = make_backing(tier, cfg.v, lo.words, backing_path,
                               P=cfg.P,
                               io_driver=cfg.io_driver,
                               io_queue_depth=cfg.io_queue_depth,
                               stats=self.tier_stats, ledger=self.ledger,
                               shard_stats=self.shard_stats,
                               shard_ledgers=self.shard_ledgers,
                               checksum=cfg.checksums,
                               fault_spec=cfg.fault_spec,
                               io_retries=cfg.io_retries,
                               io_backoff_s=cfg.io_backoff_s)
        self.backing = backing
        if cfg.trace:
            # Attach each shard's tracer to its engine and down the driver
            # wrapper chain (faulty/sanitize proxies), duck-typed like the
            # note_submit/note_complete hooks — no constructor churn.
            shards = getattr(backing, "shards", None) or [backing]
            for p, sh in enumerate(shards):
                tr = self.shard_tracers[min(p, len(self.shard_tracers) - 1)]
                eng = getattr(sh, "engine", None)
                if eng is not None:
                    eng.tracer = tr
                f = getattr(sh, "file", None)
                while f is not None:
                    if hasattr(f, "tracer"):
                        f.tracer = tr
                    f = getattr(f, "inner", None)
        store = TieredStore(lo, backing, self.ledger,
                            shard_ledgers=self.shard_ledgers)
        if init_fn is not None:
            # Populate k contexts at a time so the device never holds more
            # than the resident partitions, even during init.
            def one(rho):
                ctx = Ctx(lo, jnp.zeros((lo.words,), jnp.uint32))
                for name, val in init_fn(rho).items():
                    ctx = ctx.set(name, val)
                return ctx.words

            chunk = jax.jit(jax.vmap(one))
            for r0 in range(0, cfg.v, cfg.k):
                rhos = jnp.arange(r0, r0 + cfg.k, dtype=jnp.int32)
                # Init population is input loading, deliberately outside the
                # IOLedger: the Lemma 7.1.7/7.1.9 closed forms (and the
                # pinned measured-vs-modeled tests) cover the algorithm's
                # supersteps, not the one-time load of its input.
                # pems-lint: disable=ledger-balance
                backing.write_block(r0, r0 + cfg.k, np.asarray(chunk(rhos)))
        return store

    def store_spec(self) -> P:
        return P(self.cfg.vp_axis, None)

    # -------------------------------------------------------------- superstep
    def superstep(
        self,
        store: ContextStore,
        fn: Callable[[jnp.ndarray, Ctx], Ctx],
        reads: Optional[Sequence[str]] = None,
        writes: Optional[Sequence[str]] = None,
        name: str = "superstep",
        procs: Optional[Sequence[int]] = None,
        stream: bool = False,
    ) -> ContextStore:
        """Run one computation superstep: ``fn(rho, ctx) -> ctx`` for every
        virtual processor, in rounds of ``P·k``.

        ``reads``/``writes`` declare the touched fields for the ``sliced``
        driver (and tighten the ledger); with the ``explicit``/``async``
        drivers the full live context swaps.

        ``procs`` (tiered stores only) restricts the superstep to the named
        processes' shards — contexts ``[p·v/P, (p+1)·v/P)`` per listed
        ``p`` — touching only those shards' backings/ledgers.  This is the
        per-process recovery entry point: re-running a stage with
        ``procs=[p]`` after shard ``p``'s disk failed leaves the other
        shards byte-for-byte untouched.  Default: every process.

        ``stream`` (disk backing tiers only; ignored elsewhere) marks an
        I/O-bound stage — PSRS's k-way merge over the received buckets —
        whose round swap-ins should be prefetched through the block API
        while the previous round computes *regardless* of the configured
        driver, so merge compute overlaps disk reads even under
        ``driver="explicit"``.  Results are bit-identical (rounds touch
        disjoint rows); ``TierStats.merge_prefetch_events`` counts the
        overlapped swap-ins and ``merge_stall_s`` the residual blocking.
        """
        if (not isinstance(store, TieredStore)
                and isinstance(store.data, jax.core.Tracer)):
            # Inside a jitted program a span would time the trace, once;
            # the stage's own named scope labels its device operations.
            return self._superstep_impl(store, fn, reads, writes, procs,
                                        stream)
        with self.tracer.span(f"superstep:{name}", tid="supersteps",
                              cat="superstep", driver=self.cfg.driver,
                              stream=stream):
            return self._superstep_impl(store, fn, reads, writes, procs,
                                        stream)

    def _superstep_impl(self, store, fn, reads, writes, procs, stream):
        cfg = self.cfg
        lo = self.layout
        sliced = cfg.driver == "sliced" and reads is not None and writes is not None

        self._ledger_superstep(sliced, reads, writes, procs)

        if isinstance(store, TieredStore):
            return self._superstep_tiered(store, fn, reads, writes, sliced,
                                          procs, stream)
        if procs is not None:
            raise ValueError(
                "procs= is a tiered-store knob (per-shard recovery); the "
                "device tier runs every process in one traced program")

        if sliced:
            body = self._round_body_sliced(fn, list(reads), list(writes))
        else:
            body = self._round_body_full(fn)

        if cfg.P == 1:
            data = self._run_rounds(store.data, body, dev=None)
        else:
            def per_device(local):
                dev = lax.axis_index(cfg.vp_axis)
                return self._run_rounds(local, body, dev=dev)

            # check_vma=False: superstep bodies are application code written
            # per context, unaware of the mesh; a loop carry they start from
            # a constant (e.g. kway_merge's splitter search) would otherwise
            # be refused for not varying over the vp axis.
            data = jax.shard_map(
                per_device,
                mesh=self.mesh,
                in_specs=(P(cfg.vp_axis, None),),
                out_specs=P(cfg.vp_axis, None),
                check_vma=False,
            )(store.data)
        return ContextStore(lo, data)

    # ------------------------------------------------- tiered (host-driven)
    def _superstep_tiered(self, store: TieredStore, fn, reads, writes,
                          sliced: bool, procs=None,
                          stream: bool = False) -> TieredStore:
        """Host-driven round pipeline over a host/memmap backing store.

        Per round: swap in the round's ``k`` contexts (live/declared words
        only), run the jitted round body on device, swap the results out.
        The ``async`` driver prefetches round ``r+1`` on a worker thread
        while round ``r`` computes (double buffering, §5.1).
        """
        lo = self.layout
        if sliced:
            in_idx = field_word_index(lo, reads)
            out_idx = field_word_index(lo, writes)
        else:
            # Full-context swap, but live allocator bytes only (§6.6).
            in_idx = out_idx = lo.live_word_index()
        body = self._tiered_body(fn, in_idx, out_idx)
        self._run_tiered(store, body, in_idx, out_idx, procs, stream)
        return store

    def _tiered_body(self, fn, in_idx, out_idx):
        lo, k = self.layout, self.cfg.k
        # The index maps are runtime arguments, not trace constants: embedded
        # million-word iota comparisons otherwise send XLA constant folding
        # off a cliff (seconds per superstep compile).
        in_j = None if in_idx is None else jnp.asarray(in_idx, jnp.int32)
        out_j = None if out_idx is None else jnp.asarray(out_idx, jnp.int32)

        # Cache the jitted body per stage function: jax.jit keys on function
        # identity, so a fresh closure here would re-trace and recompile the
        # stage on *every* superstep call (ruinous for big traces like the
        # unrolled k-way merge network).  Everything else the trace depends
        # on is the layout and k (in the key below), a runtime argument
        # (rw, in_j/out_j — index *contents* never shape a trace), or part
        # of jit's own cache key (shapes; None-ness via pytree structure).
        # The cache outlives the executor, so a later job that runs the
        # same stage function on the same layout neither traces nor lowers.
        try:
            cache = _TIERED_BODIES.setdefault(fn, {})
            stage = weakref.ref(fn)    # the cache must not keep fn alive
        except TypeError:          # fn not weakref-able: run uncached
            cache, stage = {}, lambda: fn
        key = (_layout_key(lo), k)
        body = cache.get(key)
        if body is None:
            @jax.jit
            def body(rho0, rw, in_i, out_i):   # rw: [k, n_in] uint32
                rhos = rho0 + jnp.arange(k, dtype=jnp.int32)

                def one(rho, r):
                    if in_i is None:
                        w = r
                    else:
                        # Same zero-fill convention as the sliced device
                        # driver: undeclared (or dead) words are simply not
                        # resident.
                        w = jnp.zeros((lo.words,), jnp.uint32).at[in_i].set(
                            r, indices_are_sorted=True, unique_indices=True
                        )
                    out = stage()(rho, Ctx(lo, w)).words
                    if out_i is None:
                        return out
                    return out.take(out_i)

                # Pinned row-major like the device tier's round blocks.
                return row_major(jax.vmap(one)(rhos, row_major(rw)))

            cache[key] = body

        return lambda rho0, rw: body(rho0, rw, in_j, out_j)

    def _run_tiered(self, store: TieredStore, body, in_idx, out_idx,
                    procs=None, stream: bool = False) -> None:
        """Drive the round pipeline once per (selected) process: process
        ``p`` swaps its own ``v/P`` contexts through its own shard of the
        backing — its own file, engine, ledger, and stats — in ``v/(P·k)``
        rounds.  ``procs=None`` runs every process (ID order, §6.5); a
        subset re-runs only those shards (per-process recovery)."""
        for p in (range(self.cfg.P) if procs is None else procs):
            self._run_tiered_proc(store, body, in_idx, out_idx, p, stream)

    def _run_tiered_proc(self, store: TieredStore, body, in_idx, out_idx,
                         p: int, stream: bool = False) -> None:
        cfg = self.cfg
        stats, led = self.shard_stats[p], self.shard_ledgers[p]
        bk = store.backing
        disk = bk.disk
        k = cfg.k
        base = p * cfg.v_local
        rounds = cfg.v_local // k
        # A streamed stage (PSRS merge) prefetches its round swap-ins on a
        # disk backing under *every* driver — the stage is I/O bound by
        # construction, so the explicit/sliced drivers get the §5.1 overlap
        # for it too.  Bit-identical: rounds touch disjoint context rows.
        streamed = stream and disk and rounds > 1
        use_async = (cfg.driver == "async" or streamed) and rounds > 1
        # The shard whose engine this process drives (the whole backing at
        # P == 1 — the two are the same object then).
        shard = bk.shards[p] if hasattr(bk, "shards") else bk
        # Engine-backed tier + async driver: leave the writeback in flight on
        # the submission queue instead of blocking the round loop — rounds
        # touch disjoint context rows, so the only ordering requirement is
        # the final drain.  Round r's compute then overlaps round r+1's
        # swap-in (prefetch thread) AND round r-1's swap-out (engine queue):
        # true read+write overlap, measured by TierStats.rw_overlap_events.
        async_writeback = (use_async
                           and getattr(shard, "engine", None) is not None)
        # Span lane for this process: the prefetch thread's swap_in spans
        # land on their own tid, so the Perfetto view shows them genuinely
        # overlapping the rounds lane's compute spans.  Every complete()
        # below reuses the exact t0/t1 the stats were billed with — the
        # trace and TierStats can never disagree.
        tracer = self.shard_tracers[min(p, len(self.shard_tracers) - 1)]

        def fetch(r):
            t0 = time.perf_counter()
            r0 = base + r * k
            h = bk.read_block(r0, r0 + k, cols=in_idx)
            d = jax.device_put(h)
            d.block_until_ready()
            led.add_tier_in(h.nbytes, disk)
            t1 = time.perf_counter()
            stats.swap_in_s += t1 - t0
            tracer.complete("swap_in", t0, t1, tid="prefetch", cat="io",
                            round=r, bytes=h.nbytes)
            return d

        pool = ThreadPoolExecutor(max_workers=1) if use_async else None
        try:
            nxt = pool.submit(fetch, 0) if use_async else None
            for r in range(rounds):
                if use_async:
                    t0 = time.perf_counter()
                    blk = nxt.result()
                    t1 = time.perf_counter()
                    dt = t1 - t0
                    stats.stall_s += dt
                    tracer.complete("stall", t0, t1, tid="rounds",
                                    cat="stall", round=r)
                    if streamed:
                        stats.merge_stall_s += dt
                    if r + 1 < rounds:
                        # Safe to overlap with round r's writeback: rounds
                        # touch disjoint context rows.
                        nxt = pool.submit(fetch, r + 1)
                        if streamed:
                            # This swap-in runs while round r's compute is
                            # in flight — the measurable merge/read overlap.
                            stats.merge_prefetch_events += 1
                else:
                    t0 = time.perf_counter()
                    blk = fetch(r)
                    t1 = time.perf_counter()
                    stats.stall_s += t1 - t0
                    tracer.complete("stall", t0, t1, tid="rounds",
                                    cat="stall", round=r)

                t0 = time.perf_counter()
                out = body(jnp.int32(base + r * k), blk)   # async dispatch
                out_h = np.asarray(out)                    # blocks on compute
                t1 = time.perf_counter()
                stats.compute_s += t1 - t0
                tracer.complete("compute", t0, t1, tid="rounds",
                                cat="compute", round=r)

                t0 = time.perf_counter()
                r0 = base + r * k
                bk.write_block(r0, r0 + k, out_h, cols=out_idx,
                               wait=not async_writeback)
                led.add_tier_out(out_h.nbytes, disk)
                t1 = time.perf_counter()
                stats.swap_out_s += t1 - t0
                tracer.complete("swap_out", t0, t1, tid="rounds", cat="io",
                                round=r, bytes=out_h.nbytes)
                stats.rounds += 1
                if self.cursors and p < len(self.cursors):
                    # Advisory progress note (atomic, not fsynced): a resume
                    # restarts the whole in-progress superstep either way,
                    # but postmortems see how far the round loop got.
                    self.cursors[p].note_round(r)
        finally:
            if pool is not None:
                pool.shutdown(wait=True)
            # Quiesce in-flight engine writebacks before anyone reads the
            # rows back (and so errors surface here, not at a later read).
            shard.drain()

    # ----------------------------------------------------------- round bodies
    def _run_rounds(self, local_data, body, dev):
        cfg = self.cfg
        v_local = local_data.shape[0]
        rounds = v_local // cfg.k
        base = jnp.int32(0) if dev is None else dev.astype(jnp.int32) * v_local

        if cfg.driver == "async" and rounds > 1:
            # Double-buffered: carry the prefetched round; issue the next
            # round's swap-in before computing the current one so the copy
            # can overlap compute.
            def sbody(carry, r):
                data, blk = row_major(carry)  # blk: prefetched round r
                nxt = lax.dynamic_slice_in_dim(
                    data, (r + 1) % rounds * cfg.k, cfg.k, axis=0
                )
                nxt = jax.lax.optimization_barrier(row_major(nxt))
                out = row_major(body(base + r * cfg.k, blk))
                data = lax.dynamic_update_slice_in_dim(
                    data, out, r * cfg.k, axis=0
                )
                return row_major((data, nxt)), None

            first = lax.dynamic_slice_in_dim(local_data, 0, cfg.k, axis=0)
            (data, _), _ = lax.scan(
                sbody, (local_data, first), jnp.arange(rounds)
            )
            return data

        # Every carried store and round block is pinned row-major (see
        # ``row_major``): left free, XLA may carry the store transposed.
        def sbody(data, r):
            data = row_major(data)
            blk = row_major(
                lax.dynamic_slice_in_dim(data, r * cfg.k, cfg.k, axis=0))
            out = row_major(body(base + r * cfg.k, blk))
            data = lax.dynamic_update_slice_in_dim(data, out, r * cfg.k, axis=0)
            return row_major(data), None

        data, _ = lax.scan(sbody, local_data, jnp.arange(rounds))
        return data

    def _round_body_full(self, fn):
        lo = self.layout

        def body(rho0, blk):  # blk: [k, words]
            rhos = rho0 + jnp.arange(self.cfg.k, dtype=jnp.int32)
            return jax.vmap(
                lambda rho, w: fn(rho, Ctx(lo, w)).words
            )(rhos, blk)

        return body

    def _round_body_sliced(self, fn, reads: List[str], writes: List[str]):
        lo = self.layout

        # One precomputed word-index map per declaration set: the union of
        # the declared fields' word ranges, sorted so the gather/scatter is a
        # monotone sweep over the context.  A superstep that declares many
        # fields (PSRS declares up to 3 reads + 3 writes) then costs one
        # take + one scatter per round instead of O(fields) slice ops.
        read_idx = jnp.asarray(field_word_index(lo, reads), jnp.int32)
        write_idx = jnp.asarray(field_word_index(lo, writes), jnp.int32)

        def body(rho0, blk):
            rhos = rho0 + jnp.arange(self.cfg.k, dtype=jnp.int32)

            def one(rho, w):
                # Only the declared read fields are "swapped in"; the rest of
                # the context view is zero-filled (reading undeclared fields
                # is an application bug, as with real mmap-backed paging the
                # bytes simply would not be resident).
                ctx_words = jnp.zeros_like(w).at[read_idx].set(
                    w.take(read_idx), indices_are_sorted=True,
                    unique_indices=True,
                )
                out = fn(rho, Ctx(lo, ctx_words))
                # Only declared writes land back in the store.
                return w.at[write_idx].set(
                    out.words.take(write_idx), indices_are_sorted=True,
                    unique_indices=True,
                )

            return jax.vmap(one)(rhos, blk)

        return body

    # ---------------------------------------------------------------- ledger
    def _ledger_superstep(self, sliced, reads, writes, procs=None):
        cfg, lo = self.cfg, self.layout
        B = cfg.block_bytes
        if sliced:
            rbytes = sum(lo.field_bytes(n) for n in reads)
            wbytes = sum(lo.field_bytes(n) for n in writes)
        else:
            rbytes = wbytes = lo.live_bytes
        # Every VP swaps in its (touched) context and swaps it back out once
        # per virtual superstep (§6.1: a careful implementation swaps each
        # context in and out exactly once).  A procs-restricted (recovery)
        # run only swaps the listed shards' contexts.
        nctx = cfg.v if procs is None else len(procs) * cfg.v_local
        self.ledger.add_swap_in(rbytes * nctx, B)
        self.ledger.add_swap_out(wbytes * nctx, B)
        self.ledger.add_barrier()

    # ------------------------------------------------------- debugging helper
    def all_rhos(self) -> jnp.ndarray:
        return jnp.arange(self.cfg.v, dtype=jnp.int32)


# Bind collective methods (defined in their own module to keep files focused).
from . import collectives as _collectives  # noqa: E402

Pems.alltoallv = _collectives.alltoallv
Pems.bcast = _collectives.bcast
Pems.gather = _collectives.gather
Pems.reduce = _collectives.reduce
Pems.allreduce = _collectives.allreduce
Pems.allgather = _collectives.allgather
