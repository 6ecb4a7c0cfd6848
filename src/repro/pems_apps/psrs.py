"""PSRS — Parallel Sorting by Regular Sampling (thesis Alg 8.3.1) on PEMS.

Four virtual supersteps, exactly the thesis' structure:

  1. local sort + choose v regular samples        (computation)
  2. **Gather** all v² samples at the root
  3. root sorts samples, picks v−1 splitters; **Bcast**
  4. partition the sorted local data by splitters; **Alltoallv** the buckets
  5. merge received buckets                        (computation)

The final Alltoallv moves the entire data set — it dominates I/O, which is
why PSRS is the thesis' flagship benchmark for direct vs indirect delivery.

Duplicate keys are handled by lexicographic (value, global-index) splitters,
which preserves the 2n/v per-receiver bound even for constant inputs.

The context is packed, about 5 words a key: the sorted ``data`` (n/v
words), whose buckets are contiguous slices described by ``v + 1`` offsets
(``boff``), the received buckets end to end in ``brecv`` (2n/v words, the
receive bound) with their offsets (``roff``), the merged ``result`` (2n/v),
and the O(v²) samples and splitters.  No field is sized per destination:
the Alltoallv is the packed form of :meth:`repro.core.Pems.alltoallv`.
"""

from __future__ import annotations

import functools
import os
import signal
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ContextLayout, Pems, PemsConfig, SuperstepCursor,
                        atomic_replace_file)
from repro.kernels.bitonic_sort import bitonic_sort
from repro.kernels.kway_merge import kway_merge, unpack_runs
from .common import INT_MAX

# Fields each stage both reads and writes: rerunning such a stage after a
# mid-stage crash would compute from possibly-torn rows, so the recoverable
# runner snapshots them before the stage and restores on a dirty resume.
# Stages absent here have disjoint read/write sets and rerun idempotently.
# (Kept as a side table so ``steps`` stays a plain (name, fn) list.)
STAGE_SNAPSHOT_FIELDS = {
    "sort_sample": ("data",),
    "bcast_splitters": ("gsplit",),
}


@functools.lru_cache(maxsize=32)
def _stage_fns(v: int, n_v: int, rcap: int, local_sort, merge_tile):
    """The four compute stages' bodies for one static configuration, built
    once: a later job of the same shape hands the executor the same
    functions, so the tiered tiers reuse their jitted round bodies instead
    of tracing and lowering them again.  ``merge_tile=None`` merges by the
    dense re-sort."""
    # One sender's bucket is at most its n/v keys, so a run of the merge
    # needs no more than n/v lanes.
    run_width = min(n_v, rcap)

    # Each stage body runs under a named scope (psrs.<stage>), so its
    # device operations carry the stage's name in the profiler's trace, in
    # the whole-program jit and in the tiered stage jits alike.  Scope
    # names hold no ":" or "/": the profiler separates on both.
    @jax.named_scope("psrs.sort_sample")
    def sort_and_sample(rho, ctx):
        with jax.named_scope("psrs.local_sort"):
            data = local_sort(ctx.get("data"))
        # Regular sampling: positions ⌊j·n_v/v⌋, j = 0..v−1 (Shi & Schaeffer).
        idx = (jnp.arange(v) * n_v) // v
        gid = rho * n_v + idx.astype(jnp.int32)
        samp = jnp.stack([data[idx], gid], axis=-1)
        return ctx.set("data", data).set("samp", samp)

    @jax.named_scope("psrs.pick_splitters")
    def pick_splitters(rho, ctx):
        allsamp = ctx.get("allsamp").reshape(-1, 2)
        order = jnp.lexsort((allsamp[:, 1], allsamp[:, 0]))
        s = allsamp[order]
        # Splitters at ranks (i+1)·v + v/2 − 1, i = 0..v−2; sentinel at end.
        ii = (jnp.arange(v - 1) + 1) * v + v // 2 - 1
        gs = jnp.concatenate(
            [s[ii], jnp.array([[INT_MAX, INT_MAX]], jnp.int32)]
        )
        return ctx.set("gsplit", gs)

    @jax.named_scope("psrs.partition")
    def partition(rho, ctx):
        data = ctx.get("data")                 # sorted by sort_sample
        gs = ctx.get("gsplit")
        sv, sg = gs[:-1, 0], gs[:-1, 1]        # v−1 splitters
        # A key goes to bucket d = #splitters (sv, sg) <= (x, gid), with
        # gid = rho·n_v + its position.  Along the sorted data (x, gid)
        # ascends, so bucket d is a slice and its end is #{(x, gid) <
        # splitter d}: the keys below sv, then those equal to it whose
        # position is below sg − rho·n_v.
        with jax.named_scope("psrs.bucket_offsets"):
            lt = jnp.searchsorted(data, sv, side="left")    # #{x < sv}
            le = jnp.searchsorted(data, sv, side="right")   # #{x <= sv}
            cut = jnp.clip(sg - rho * n_v, lt, le).astype(jnp.int32)
            boff = jnp.concatenate([jnp.zeros((1,), jnp.int32), cut,
                                    jnp.full((1,), n_v, jnp.int32)])
        return ctx.set("boff", boff)

    @jax.named_scope("psrs.merge")
    def merge(rho, ctx):
        # The received buckets lie end to end in brecv; roff[v] counts them
        # all, so a total past rcap (the overflow extract reports) is never
        # read past the field.
        recv = ctx.get("brecv")              # [rcap]
        roff = ctx.get("roff")               # [v + 1]
        if merge_tile is not None:
            # Tiled k-way merge with exact splitting: O(n log v) over the
            # already-sorted buckets instead of the O(n log n) re-sort, on
            # runs cut from brecv for the merge alone.
            runs, cnt = unpack_runs(recv, roff, run_width)
            merged, _, _ = kway_merge(runs, cnt, rcap=rcap, tile=merge_tile,
                                      fill=INT_MAX)
        else:
            lane = jnp.arange(rcap, dtype=jnp.int32)
            merged = local_sort(jnp.where(lane < roff[v], recv, INT_MAX))
        return ctx.set("result", merged)

    return sort_and_sample, pick_splitters, partition, merge


def _build(v: int, k: int, n_v: int, rcap, driver: str,
           mode: str, local_sort, use_kernel: bool = True,
           tier: str = "device", backing_path=None, device_cap_bytes=None,
           P: int = 1, mesh=None, alpha=None,
           io_driver=None, io_queue_depth=None,
           fault_spec=None, checksums: bool = False, io_retries=None,
           merge_kernel=None, merge_tile=None,
           trace: bool = False, trace_path=None):
    # The 2n/v per-receiver guarantee.
    rcap = 2 * n_v if rcap is None else rcap
    # Default local sort: the bitonic kernel (auto backend — compiled Pallas
    # on TPU, jnp.sort on CPU/GPU).  use_kernel=False keeps the seed's
    # jnp.sort on every path; both are bit-identical on int32 keys.
    if local_sort is None:
        local_sort = bitonic_sort if use_kernel else jnp.sort
    lo = (
        ContextLayout()
        .add("data", (n_v,), jnp.int32)
        .add("samp", (v, 2), jnp.int32)        # (value, global index)
        .add("allsamp", (v, v, 2), jnp.int32)
        .add("gsplit", (v, 2), jnp.int32)
        .add("boff", (v + 1,), jnp.int32)      # bucket d: data[boff[d]:boff[d+1]]
        .add("brecv", (rcap,), jnp.int32)      # received buckets, end to end
        .add("roff", (v + 1,), jnp.int32)      # roff[v]: the total received
        .add("result", (rcap,), jnp.int32)
    )
    io_kw = {}
    if io_driver is not None:
        io_kw["io_driver"] = io_driver
    if io_queue_depth is not None:
        io_kw["io_queue_depth"] = io_queue_depth
    if fault_spec is not None:
        io_kw["fault_spec"] = fault_spec
    if io_retries is not None:
        io_kw["io_retries"] = io_retries
    if checksums:
        io_kw["checksums"] = True
    if merge_kernel is not None:
        io_kw["merge_kernel"] = bool(merge_kernel)
    if merge_tile is not None:
        io_kw["merge_tile"] = merge_tile
    if trace:
        io_kw["trace"] = True
    if trace_path is not None:
        io_kw["trace_path"] = trace_path
    pems = Pems(PemsConfig(v=v, k=k, P=P, driver=driver, tier=tier,
                           backing_path=backing_path, alpha=alpha,
                           device_cap_bytes=device_cap_bytes, **io_kw),
                lo, mesh=mesh)

    sort_and_sample, pick_splitters, partition, merge = _stage_fns(
        v, n_v, rcap, local_sort,
        pems.cfg.merge_tile if pems.cfg.merge_kernel and use_kernel
        else None)

    # The program as an explicit stage list: the device tier jit-fuses the
    # whole pipeline as before, while backing tiers run it stage-by-stage
    # host-side — and callers (checkpoint tests, resumable jobs) can stop
    # after any stage and resume from a restored store.
    # Every stage accepts an optional ``procs`` (tiered stores only): run the
    # stage for the listed processes' shards alone — the per-process
    # recovery hook psrs_run_recoverable drives after a one-disk failure.
    steps = [
        ("sort_sample", lambda st, procs=None: pems.superstep(
            st, sort_and_sample, reads=["data"], writes=["data", "samp"],
            procs=procs)),
        ("gather_samples", lambda st, procs=None: pems.gather(
            st, "samp", "allsamp", root=0, procs=procs)),
        ("pick_splitters", lambda st, procs=None: pems.superstep(
            st, pick_splitters, reads=["allsamp"], writes=["gsplit"],
            procs=procs)),
        ("bcast_splitters", lambda st, procs=None: pems.bcast(
            st, "gsplit", root=0, procs=procs)),
        ("partition", lambda st, procs=None: pems.superstep(
            st, partition, reads=["data", "gsplit"], writes=["boff"],
            procs=procs)),
        ("alltoallv", lambda st, procs=None: pems.alltoallv(
            st, "data", "brecv", offsets=("boff", "roff"),
            mode=mode, fill=INT_MAX, use_kernel=use_kernel, procs=procs)),
        # stream=True: on a disk backing the merge's bucket reads are
        # prefetched through the block API while the previous round merges,
        # under every driver (TierStats.merge_prefetch_events counts them).
        ("merge", lambda st, procs=None: pems.superstep(
            st, merge, reads=["brecv", "roff"], writes=["result"],
            procs=procs, stream=True)),
    ]

    # Stage spans on the main tracer's "stages" lane, for the host-driven
    # paths (backing tiers, the P > 1 mesh, callers of psrs_plan): one per
    # plan stage, the unit the obs report attributes compute/I-O/stall time
    # to.  The jitted program below runs the bare steps: there a span would
    # fire once at trace time, and the stages' named scopes label the work.
    def _staged(name, fn):
        def run(st, procs=None):
            with pems.tracer.span(f"stage:{name}", tid="stages",
                                  cat="stage"):
                return fn(st, procs=procs)
        return run

    staged = [(name, _staged(name, fn)) for name, fn in steps]

    def load(data_blocks):                  # [v, n_v] int32
        store = pems.init()
        if pems.mesh is not None:
            # Each device receives its own contexts' rows (and an explicit
            # mesh requires the update to match the store's sharding).
            data_blocks = jax.device_put(data_blocks, store.data.sharding)
        return store.with_field("data", data_blocks)

    def extract(store):
        # Each context's received total, [v, 1], and whether it overflowed.
        total = store.field("roff")[:, v:]
        return store.field("result"), total, total > rcap

    # The single-process device tier jit-fuses the whole pipeline, traced
    # or not, running the bare steps; the P > 1 mesh path runs the staged
    # steps eagerly (each superstep/collective shard_maps and jits
    # internally).
    jitted = tier == "device" and P == 1

    def program(data_blocks):
        store = load(data_blocks)
        for _, step in (steps if jitted else staged):
            store = step(store)
        return extract(store)

    if jitted:
        program = jax.jit(program)
    return pems, program, (load, staged, extract)


def psrs_plan(
    v: int,
    n_v: int,
    k: int = 1,
    driver: str = "explicit",
    mode: str = "direct",
    rcap: Optional[int] = None,
    local_sort=None,
    use_kernel: bool = True,
    tier: str = "device",
    backing_path=None,
    device_cap_bytes=None,
    P: int = 1,
    mesh=None,
    alpha=None,
    io_driver=None,
    io_queue_depth=None,
    fault_spec=None,
    checksums: bool = False,
    io_retries=None,
    merge_kernel: Optional[bool] = None,
    merge_tile: Optional[int] = None,
    trace: bool = False,
    trace_path: Optional[str] = None,
):
    """Stepwise PSRS: returns ``(pems, load, steps, extract)``.

    ``load([v, n_v] int32) -> store`` initialises the population;
    ``steps`` is a list of named ``store -> store`` stages (run them in
    order, or stop after any stage, checkpoint the backing store, and
    resume later); ``extract(store) -> (result, rcount, oflow)``.

    ``trace=True`` records structured spans (stages, supersteps, executor
    rounds, I/O requests, collective chunks) into ``pems.tracer``, the
    steps being run from the host; export with
    ``pems.export_trace(path)`` (or set ``trace_path`` — :func:`psrs_sort`
    / :func:`psrs_run_recoverable` then export automatically).
    """
    pems, _, (load, steps, extract) = _build(
        v, k, n_v, rcap, driver, mode, local_sort,
        use_kernel=use_kernel, tier=tier, backing_path=backing_path,
        device_cap_bytes=device_cap_bytes, P=P, mesh=mesh, alpha=alpha,
        io_driver=io_driver, io_queue_depth=io_queue_depth,
        fault_spec=fault_spec, checksums=checksums, io_retries=io_retries,
        merge_kernel=merge_kernel, merge_tile=merge_tile,
        trace=trace, trace_path=trace_path,
    )
    return pems, load, steps, extract


def psrs_sort(
    keys,
    v: int,
    k: int = 1,
    driver: str = "explicit",
    mode: str = "direct",
    rcap: Optional[int] = None,
    local_sort=None,
    return_pems: bool = False,
    use_kernel: bool = True,
    tier: str = "device",
    backing_path=None,
    device_cap_bytes=None,
    P: int = 1,
    mesh=None,
    alpha=None,
    io_driver=None,
    io_queue_depth=None,
    fault_spec=None,
    checksums: bool = False,
    io_retries=None,
    merge_kernel: Optional[bool] = None,
    merge_tile: Optional[int] = None,
    trace: bool = False,
    trace_path: Optional[str] = None,
):
    """Sort int32 ``keys`` ([n], n divisible by v) with PSRS on PEMS.

    ``mode`` selects PEMS2 direct delivery or the PEMS1 indirect baseline for
    the final Alltoallv; ``rcap`` is the per-receiver capacity (defaults to
    the PSRS guarantee 2n/v).  The context is packed (about 5 words a key,
    see the module docstring), so messages need no capacity of their own.
    ``use_kernel`` toggles the kernel paths end to end — the fused Pallas
    delivery in the final Alltoallv, the bitonic local sort, and the tiled
    k-way merge; ``False`` keeps the seed's dense/jnp.sort routes (results
    are bit-identical either way; kept for equivalence testing).  ``merge_kernel``/
    ``merge_tile`` (defaults from :class:`~repro.core.PemsConfig`) control
    the merge stage alone: the exact-splitter tiled merge of the v received
    sorted buckets — O(n log v) instead of the dense O(n log n) re-sort —
    in ``merge_tile``-wide output tiles, with its input buckets streamed
    through the backing block API on disk tiers so merge compute overlaps
    the reads (``pems.tier_stats.merge_prefetch_events``).  ``local_sort``
    overrides the local-sort primitive (default: the ``bitonic_sort``
    kernel with automatic backend dispatch; ``jnp.sort`` when
    ``use_kernel=False``).

    ``tier`` selects where the context population lives: ``"device"`` (the
    seed in-memory path, whole program jitted), ``"host"`` (host RAM),
    ``"memmap"`` (a disk backing file at ``backing_path``) or ``"file"``
    (the same file reached through the :mod:`repro.io` async engine —
    ``io_driver`` picks ``buffered``/``odirect``/``mmap``,
    ``io_queue_depth`` bounds in-flight requests) — the out-of-core paths,
    host-driven with only k·μ device-resident at a time, optionally
    enforced via ``device_cap_bytes``.  All tiers sort bit-identically.

    ``P``/``mesh`` run the simulation over ``P`` real processors: each
    process owns ``v/P`` contexts.  On the device tier a jax mesh with the
    ``vp`` axis in Auto mode is required
    (:func:`repro.launch.mesh.make_mesh_auto`) and the final Alltoallv's network phase is
    α-chunked over the mesh (``alpha``, Alg 7.1.3) — through the fused
    (src_proc, dst_proc)-tiled delivery kernel by default, bit-identical to
    the dense ``use_kernel=False`` route and to the ``P == 1`` reference.
    On a backing tier ``P > 1`` needs no mesh: the backing is *sharded* —
    each process owns its own ``v/P``-row backing file
    (``backing_path + ".shard<p>"``, its own I/O engine on ``tier="file"``)
    and the round pipeline and collectives run per process, staging the
    network phase through per-process host buffers.  Per-shard traffic is
    measured in ``pems.shard_ledgers[p]``/``pems.shard_stats[p]`` and sums
    to the ``P == 1`` totals; results stay bit-identical.

    Every call records four spans on the ``calls`` lane:
    ``call:prepare`` (keys to the device, the plan built),
    ``call:dispatch`` (the program called: on the device tier its trace,
    lowering, compile or cache load and enqueue; on the host-driven tiers
    the whole run), ``call:wait`` (the results fetched to the host) and
    ``call:extract`` (the sorted keys assembled).  They are annotations of
    a ``jax.profiler`` session always, and enter the :mod:`repro.obs` ring
    with ``trace=True``, which also records the host-driven paths'
    per-stage and per-superstep spans, executor rounds (compute vs
    swap_in/swap_out vs stall), per-request engine I/O and collective
    chunks.  The device tier runs the same jitted program either way; its
    stages are ``jax.named_scope`` scopes (``psrs.<stage>``) on the device
    operations.  Results are bit-identical.  With ``trace_path`` set the
    merged Chrome/Perfetto trace (plus a metrics snapshot) is written there
    on completion; inspect with ``python -m repro.obs report <path>``.

    Raises ``ValueError`` for n not divisible by v (and for any invalid
    :class:`~repro.core.PemsConfig` combination) and ``OverflowError``
    when a context receives more than ``rcap`` keys.
    """
    # call:prepare builds the tracer it is recorded in, so it is timed and
    # annotated by hand; the other call spans are ordinary spans.
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("call:prepare"):
        keys = jnp.asarray(keys, jnp.int32)
        n = keys.shape[0]
        if n % v:
            raise ValueError(f"n={n} must be divisible by v={v}")
        n_v = n // v
        pems, program, _ = _build(
            v, k, n_v, rcap, driver, mode, local_sort,
            use_kernel=use_kernel, tier=tier, backing_path=backing_path,
            device_cap_bytes=device_cap_bytes, P=P, mesh=mesh, alpha=alpha,
            io_driver=io_driver, io_queue_depth=io_queue_depth,
            fault_spec=fault_spec, checksums=checksums,
            io_retries=io_retries, merge_kernel=merge_kernel,
            merge_tile=merge_tile, trace=trace, trace_path=trace_path)
        data = keys.reshape(v, n_v)
        if tier != "device":
            data = np.asarray(data)
    tr = pems.tracer
    tr.complete("call:prepare", t0, time.perf_counter(), tid="calls",
                cat="call")
    with tr.span("call:dispatch", tid="calls", cat="call", keys=n):
        result, rcount, oflow = program(data)
    with tr.span("call:wait", tid="calls", cat="call"):
        result = np.asarray(result)
        rcount = np.asarray(rcount)[:, 0]
        overflow = bool(np.asarray(oflow).any())
    if not overflow:
        with tr.span("call:extract", tid="calls", cat="call"):
            out = np.concatenate([result[i, : rcount[i]] for i in range(v)])
    if pems.cfg.trace_path is not None:
        pems.export_trace()
    if overflow:
        raise OverflowError(
            f"PSRS receive capacity exceeded; raise rcap (rcap={rcap})")
    if return_pems:
        return out, pems
    return out


def _snapshot_path(state_dir: str, proc: int = 0, nprocs: int = 1) -> str:
    """Per-process snapshot file; the bare legacy name at ``nprocs == 1``
    so existing single-process state dirs resume unchanged."""
    if nprocs == 1:
        return os.path.join(state_dir, "stage_snapshot.npz")
    return os.path.join(state_dir, f"stage_snapshot.p{proc}.npz")


def _save_snapshot(state_dir: str, stage: int, fields: dict,
                   proc: int = 0, nprocs: int = 1) -> None:
    """Atomically persist the pre-stage copy of the stage's read∩write
    fields (restored before a dirty rerun — see STAGE_SNAPSHOT_FIELDS).
    At ``nprocs > 1`` the fields hold process ``proc``'s shard rows only."""
    path = _snapshot_path(state_dir, proc, nprocs)
    atomic_replace_file(
        path, lambda f: np.savez(f, __stage__=np.int64(stage), **fields),
        binary=True)


def _load_snapshot(state_dir: str, stage: int,
                   proc: int = 0, nprocs: int = 1):
    """The snapshot's field dict, iff it belongs to ``stage``."""
    try:
        with np.load(_snapshot_path(state_dir, proc, nprocs)) as z:
            if int(z["__stage__"]) != stage:
                return None
            return {k: z[k] for k in z.files if k != "__stage__"}
    except (OSError, ValueError, KeyError):
        return None


def psrs_run_recoverable(
    keys,
    v: int,
    *,
    state_dir: str,
    k: int = 1,
    P: int = 1,
    alpha: Optional[int] = None,
    driver: str = "explicit",
    mode: str = "direct",
    rcap: Optional[int] = None,
    local_sort=None,
    use_kernel: bool = True,
    tier: str = "file",
    io_driver=None,
    io_queue_depth=None,
    fault_spec=None,
    checksums: bool = True,
    io_retries=None,
    device_cap_bytes=None,
    crash_after_stage=None,
    crash_in_stage=None,
    return_pems: bool = False,
    merge_kernel: Optional[bool] = None,
    merge_tile: Optional[int] = None,
    trace: bool = False,
    trace_path: Optional[str] = None,
):
    """PSRS with durable superstep recovery: survives ``kill -9``.

    Runs the :func:`psrs_plan` stages against a backing file in
    ``state_dir``, recording a durable :class:`SuperstepCursor` around every
    stage and an atomic pre-stage snapshot of the fields the stage both
    reads and writes (see ``STAGE_SNAPSHOT_FIELDS`` — rerunning those from
    possibly-torn rows would be garbage-from-garbage).  Killed at *any*
    point — between stages, mid-stage, even mid-``pwrite`` — a rerun with
    the same arguments resumes from the last completed stage and produces
    output bit-identical to an uninterrupted run.

    ``P > 1`` runs the parallel disk model: the backing is sharded into
    ``P`` per-process files (each with its own engine on ``tier="file"``)
    and recovery state is **per process** — one cursor
    (``cursor.p<p>.json``) and one snapshot per shard, each stage committed
    shard by shard (run with ``procs=[p]``, flushed via the shard's own
    backing).  A failure on one shard's disk — e.g. a
    ``fault_spec="shard=1;..."`` injection — leaves the other processes'
    cursors at the completed stage; the rerun re-executes only the failed
    process's stage against its own shard, without touching (or re-running)
    the healthy shards.  Output stays bit-identical to the ``P == 1`` run.

    ``checksums`` (default on) adds per-block CRCs to the backing file so a
    torn write in the in-progress stage is detected and healed by the rerun
    instead of silently merged; a torn write can only live in the
    in-progress stage because completed stages are flushed before their
    cursor commit.

    ``crash_after_stage`` / ``crash_in_stage`` (stage name or index;
    ``"load"`` is stage 0) SIGKILL the process at the stage boundary /
    between the stage's compute and its flush (at ``P > 1``: after the
    last process's compute, so earlier processes have already committed) —
    the chaos-test hooks.

    Raises ``ValueError`` for a non-disk ``tier`` or n not divisible by v,
    and ``OverflowError`` when a context receives more than ``rcap`` keys.
    """
    keys = np.asarray(keys, np.int32)
    n = keys.size
    if n % v:
        raise ValueError(f"n={n} must be divisible by v={v}")
    if tier not in ("memmap", "file"):
        raise ValueError(
            f"recovery needs a disk tier ('memmap' or 'file'), got {tier!r}")
    n_v = n // v
    os.makedirs(state_dir, exist_ok=True)
    backing_path = os.path.join(state_dir, "ctx.bin")
    pems, _load_unused, steps, extract = psrs_plan(
        v, n_v, k=k, P=P, alpha=alpha, driver=driver, mode=mode,
        rcap=rcap,
        local_sort=local_sort, use_kernel=use_kernel, tier=tier,
        backing_path=backing_path, device_cap_bytes=device_cap_bytes,
        io_driver=io_driver, io_queue_depth=io_queue_depth,
        fault_spec=fault_spec, checksums=checksums, io_retries=io_retries,
        merge_kernel=merge_kernel, merge_tile=merge_tile,
        trace=trace, trace_path=trace_path)

    m_ctx = v // P                        # contexts per process
    data_blocks = keys.reshape(v, n_v)

    # "load" is stage 0 (idempotent: rewrites data from the caller's input).
    # pems.init() runs exactly once below, so load goes through with_field
    # rather than psrs_plan's own load() (which would init a second engine
    # on the same backing file).
    def load_stage(st, procs=None):
        for p in (range(P) if procs is None else procs):
            st = st.with_field_rows(
                "data", p * m_ctx, data_blocks[p * m_ctx:(p + 1) * m_ctx])
        return st

    stages = [("load", load_stage)] + list(steps)

    def _stage_index(which):
        if which is None:
            return None
        if isinstance(which, str):
            for i, (name, _) in enumerate(stages):
                if name == which:
                    return i
            raise ValueError(f"unknown stage {which!r}")
        return int(which)

    crash_after = _stage_index(crash_after_stage)
    crash_in = _stage_index(crash_in_stage)

    cursors = [SuperstepCursor(SuperstepCursor.path_for(state_dir, p, P))
               for p in range(P)]
    for p, cur in enumerate(cursors):
        cur.tracer = pems.tracer
        cur.trace_tid = f"recovery.p{p}" if P > 1 else "recovery"
    pems.cursors = cursors

    store = pems.init()      # create-or-reuse: committed rows are kept
    bk = store.backing
    for p in range(P):
        st = cursors[p].state()
        in_prog = None if st is None else st.get("in_progress")
        if in_prog is None:
            continue
        if getattr(bk, "checksum", None) is not None:
            # The sidecar records *intended* CRCs for writes the crash may
            # have torn; those rows belong to the in-progress stage and are
            # about to be regenerated, so re-bless the bytes on disk —
            # only the dirty process's shard under a sharded backing.
            if hasattr(bk, "shards"):
                bk.recompute_checksums(shard=p)
            else:
                bk.recompute_checksums()
        snap = _load_snapshot(state_dir, int(in_prog), p, P)
        if snap is not None:
            with pems.tracer.span("snapshot:restore", tid="recovery",
                                  cat="recovery", proc=p,
                                  stage=int(in_prog)):
                for fname, val in snap.items():
                    store = store.with_field_rows(fname, p * m_ctx, val)

    for i, (name, fn) in enumerate(stages):
        todo = [p for p in range(P) if i > cursors[p].completed]
        for p in todo:
            fields = STAGE_SNAPSHOT_FIELDS.get(name, ())
            if fields:
                with pems.tracer.span("snapshot:save", tid="recovery",
                                      cat="recovery", proc=p, stage=i):
                    _save_snapshot(
                        state_dir, i,
                        {f: np.asarray(
                            store.field_rows(f, p * m_ctx, (p + 1) * m_ctx))
                         for f in fields},
                        p, P)
            cursors[p].mark_in_progress(i, name)
            store = fn(store, procs=[p])
            if crash_in == i and p == todo[-1]:
                os.kill(os.getpid(), signal.SIGKILL)
            # Commit this process's writes only: its shard's backing (and
            # sidecar) flush before its cursor advances.  Stages write
            # nothing outside the listed shard, so the other processes'
            # committed bytes are untouched either way.
            if hasattr(bk, "flush_shard"):
                bk.flush_shard(p)
            else:
                store.flush()
            cursors[p].mark_completed(i, name)
        if todo and crash_after == i:
            os.kill(os.getpid(), signal.SIGKILL)

    if pems.cfg.trace_path is not None:
        pems.export_trace()
    result, rcount, oflow = extract(store)
    result = np.asarray(result)
    rcount = np.asarray(rcount)[:, 0]
    if np.asarray(oflow).any():
        raise OverflowError(
            f"PSRS receive capacity exceeded; raise rcap (rcap={rcap})")
    out = np.concatenate([result[i, : rcount[i]] for i in range(v)])
    if return_pems:
        return out, pems
    return out
