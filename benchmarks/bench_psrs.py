"""Figs 8.2–8.6 analogue: PSRS on PEMS2 (direct) vs PEMS1 (indirect) vs the
hand-built EM sort stand-in (jnp.sort ≙ STXXL), scaling the problem via v;
plus the P-scaling I/O model (wall-clock P>1 needs real hosts).

Three instrumented sections land in ``BENCH_psrs.json``
(``BENCH_psrs.smoke.json`` under ``BENCH_FAST=1``/``--smoke``):

* ``phases`` — per-stage wall clock of one ``psrs_plan`` run on the memmap
  tier (whose executor jits each stage body — the device tier only jits
  the fused whole program), grouped into the thesis' three buckets: ``local_sort_s`` (sort_sample),
  ``network_s`` (sampling collectives + partition + alltoallv) and
  ``merge_s``; ``merge_dense_s`` is the same merge stage re-timed with
  ``merge_kernel=False`` for the end-to-end view of what the kernel buys.
* ``merge`` — the *paired-sample* kernel-vs-dense statistic the regression
  gate holds: on authentic post-delivery buckets (the runs of the real
  packed ``brecv``, cut by its ``roff`` offsets after running the plan
  through alltoallv), the tiled
  k-way merge and the seed's dense ``jnp.sort(flat)[:rcap]`` re-sort run
  interleaved in the same process; ``speedup_vs_dense`` is the median of
  per-iteration (dense / kernel) wall-time ratios, so machine speed
  cancels and the ratio transfers across runner generations.  A silent
  fallback to the dense path would read as speedup ≈ 1.0 and fail the
  gate's floor.
* ``stream`` — PSRS on a disk backing: the merge superstep runs with
  ``stream=True``, so ``merge_prefetch_events`` must be nonzero (bucket
  reads submitted ahead of need, overlapping merge compute) — the gate
  fails a run whose streamed merge stopped overlapping.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import analysis
from repro.kernels.kway_merge import kway_merge, unpack_runs
from repro.pems_apps import psrs_plan, psrs_sort
from repro.pems_apps.common import INT_MAX
from .common import TRACER, emit, time_fn

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Which context field each stage writes last — blocked on for honest
# per-stage wall clock under JAX's async dispatch.
_STAGE_SYNC = {
    "sort_sample": "samp",
    "gather_samples": "allsamp",
    "pick_splitters": "gsplit",
    "bcast_splitters": "gsplit",
    "partition": "boff",
    "alltoallv": "brecv",
    "merge": "result",
}

_NETWORK_STAGES = ("gather_samples", "pick_splitters", "bcast_splitters",
                   "partition", "alltoallv")


def _run_steps(load, steps, data, until=None):
    store = load(data)
    for name, step in steps:
        store = step(store)
        if name == until:
            break
    return store


def _phase_rows(td: str, n: int, v: int, k: int, rng) -> dict:
    """One plan run, each stage timed (min of 2 after a warmup pass).

    Runs on ``tier="memmap"``: the tiered executor jits each stage *body*
    and completes its I/O before returning, so per-stage wall clock is
    honest — the device tier only jits the whole fused program, and
    stepping it stage by stage would time eager re-traces instead."""
    n_v = n // v
    data = jnp.asarray(rng.integers(-2**31, 2**31 - 1, size=(v, n_v),
                                    dtype=np.int32))
    path = os.path.join(td, "phases.bin")
    _, load, steps, extract = psrs_plan(v, n_v, k, tier="memmap",
                                        backing_path=path)
    _run_steps(load, steps, data)                      # warmup: trace + jit
    stage_s = {name: float("inf") for name, _ in steps}
    for _ in range(2):
        store = load(data)
        for name, step in steps:
            with TRACER.span(f"stage:{name}", tid="bench",
                             cat="stage") as sp:
                store = step(store)
                jax.block_until_ready(store.field(_STAGE_SYNC[name]))
            stage_s[name] = min(stage_s[name], sp.duration_s)
    result, _, oflow = extract(store)
    assert not np.asarray(oflow).any()
    assert (np.asarray(result).reshape(-1) < np.inf).all()

    # The same merge stage with the dense re-sort, for the e2e comparison
    # (the gated statistic is the paired op-level ratio in ``merge``).
    _, dload, dsteps, _ = psrs_plan(v, n_v, k, tier="memmap",
                                    backing_path=path + ".dense",
                                    merge_kernel=False)
    _run_steps(dload, dsteps, data)
    dense_s = float("inf")
    for _ in range(2):
        store = _run_steps(dload, dsteps, data, until="alltoallv")
        with TRACER.span("stage:merge_dense", tid="bench",
                         cat="stage") as sp:
            store = dict(dsteps)["merge"](store)
            jax.block_until_ready(store.field("result"))
        dense_s = min(dense_s, sp.duration_s)

    return {
        "n_words": n, "v": v, "k": k,
        "stages": {name: round(s, 5) for name, s in stage_s.items()},
        "local_sort_s": round(stage_s["sort_sample"], 5),
        "network_s": round(sum(stage_s[s] for s in _NETWORK_STAGES), 5),
        "merge_s": round(stage_s["merge"], 5),
        "merge_dense_s": round(dense_s, 5),
    }


def _merge_pair_row(n: int, v: int, k: int, tile: int, rng,
                    iters: int) -> dict:
    """Paired kernel-vs-dense merge on authentic post-delivery buckets."""
    n_v = n // v
    data = jnp.asarray(rng.integers(-2**31, 2**31 - 1, size=(v, n_v),
                                    dtype=np.int32))
    _, load, steps, _ = psrs_plan(v, n_v, k)
    store = _run_steps(load, steps, data, until="alltoallv")
    rcap = 2 * n_v
    runs, brcnt = jax.vmap(lambda r, o: unpack_runs(r, o, n_v))(
        store.field("brecv"), store.field("roff"))
    cap = runs.shape[-1]                                   # [v, v, n_v]
    # Buckets with INT_MAX past their counts, as the slab delivery left
    # them, so both sides of the pair see the same input as before.
    brecv = jax.block_until_ready(jnp.where(
        jnp.arange(cap) < brcnt[..., None], runs, INT_MAX))

    f_kernel = jax.jit(jax.vmap(
        lambda b, c: kway_merge(b, c, rcap=rcap, tile=tile,
                                fill=INT_MAX)[0]))
    f_dense = jax.jit(jax.vmap(lambda b: jnp.sort(b.reshape(-1))[:rcap]))
    out_k = jax.block_until_ready(f_kernel(brecv, brcnt))
    out_d = jax.block_until_ready(f_dense(brecv))
    assert (np.asarray(out_k) == np.asarray(out_d)).all(), \
        "kernel merge diverged from the dense re-sort"

    ratios, d_best, k_best = [], float("inf"), float("inf")
    for _ in range(iters):                 # interleaved: machine speed cancels
        with TRACER.span("merge_dense", tid="bench") as sp:
            jax.block_until_ready(f_dense(brecv))
        d_s = sp.duration_s
        with TRACER.span("merge_kernel", tid="bench") as sp:
            jax.block_until_ready(f_kernel(brecv, brcnt))
        k_s = sp.duration_s
        ratios.append(d_s / k_s)
        d_best, k_best = min(d_best, d_s), min(k_best, k_s)
    ratios.sort()
    return {
        "n_words": n, "v": v, "omega": cap, "rcap": rcap, "tile": tile,
        "dense_ms": round(d_best * 1e3, 3),
        "kernel_ms": round(k_best * 1e3, 3),
        "speedup_vs_dense": round(ratios[len(ratios) // 2], 3),
    }


def _stream_row(td: str, n: int, v: int, k: int, tier: str, driver: str,
                rng) -> dict:
    keys = rng.integers(-2**31, 2**31 - 1, size=n, dtype=np.int32)
    with TRACER.span(f"stream_{tier}_{driver}", tid="bench") as sp:
        out, pems = psrs_sort(
            keys, v=v, k=k, driver=driver, tier=tier,
            backing_path=os.path.join(td, f"stream_{tier}_{driver}.bin"),
            return_pems=True)
    wall_s = sp.duration_s
    assert (out == np.sort(keys)).all(), f"streamed sort diverged: {tier}"
    ts = pems.tier_stats
    return {
        "tier": tier, "driver": driver, "n": n, "v": v, "k": k,
        "wall_s": round(wall_s, 3),
        "merge_prefetch_events": ts.merge_prefetch_events,
        "merge_stall_s": round(ts.merge_stall_s, 4),
        "overlap_fraction": round(ts.overlap_fraction, 4),
    }


def _obs_row(td: str, n: int, v: int, k: int, rng, iters: int) -> dict:
    """Paired traced-vs-untraced PSRS: the tracing-overhead statistic.

    Interleaved in-process like the merge pair, so machine speed cancels:
    ``overhead_ratio`` is the median per-iteration (traced / untraced)
    wall-time ratio on the async file-tier sort — the configuration with
    the most instrumentation (engine request spans, round spans, stage
    spans).  The regression gate caps it (``--obs-overhead``).  One traced
    run's merged Perfetto trace is exported to ``BENCH_psrs.trace.json``
    as the CI artifact."""
    keys = rng.integers(-2**31, 2**31 - 1, size=n, dtype=np.int32)

    def run_once(trace: bool, tag: str) -> float:
        with TRACER.span(f"obs_{tag}", tid="bench") as sp:
            psrs_sort(keys, v=v, k=k, driver="async", tier="file",
                      backing_path=os.path.join(td, f"obs_{tag}.bin"),
                      trace=trace)
        return sp.duration_s

    run_once(False, "warm_plain")
    run_once(True, "warm_traced")
    ratios, plain_best, traced_best = [], float("inf"), float("inf")
    for _ in range(iters):
        p_s = run_once(False, "plain")
        t_s = run_once(True, "traced")
        ratios.append(t_s / p_s)
        plain_best, traced_best = min(plain_best, p_s), min(traced_best, t_s)
    ratios.sort()

    _, pems = psrs_sort(keys, v=v, k=k, driver="async", tier="file",
                        backing_path=os.path.join(td, "obs_artifact.bin"),
                        trace=True, return_pems=True)
    pems.export_trace(os.path.join(REPO_ROOT, "BENCH_psrs.trace.json"))
    return {
        "tier": "file", "driver": "async", "n": n, "v": v, "k": k,
        "plain_s": round(plain_best, 4),
        "traced_s": round(traced_best, 4),
        "overhead_ratio": round(ratios[len(ratios) // 2], 3),
    }


def _figures(smoke: bool, rng) -> None:
    """The original Fig 8.2–8.6 CSV rows (unchanged semantics)."""
    sizes = (1 << 16,) if smoke else (1 << 16, 1 << 18, 1 << 20)
    for n in sizes:
        x = rng.integers(-2**31, 2**31 - 1, size=n, dtype=np.int32)
        v, k = 16, 4

        for mode in ("direct", "indirect"):
            out, pems = psrs_sort(x, v=v, k=k, mode=mode, return_pems=True)
            assert (out == np.sort(x)).all()
            us = time_fn(lambda: psrs_sort(x, v=v, k=k, mode=mode), iters=1)
            led = pems.ledger
            emit(f"psrs_{mode}_n{n}", us,
                 f"io={led.io_total};swap={led.swap_total};"
                 f"msg_ind={led.msg_indirect};disk={led.disk_space}")

        us = time_fn(lambda: np.asarray(jnp.sort(jnp.asarray(x))), iters=2)
        emit(f"stxxl_stand_in_jnp_sort_n{n}", us, "baseline")

    # Fig 8.6: relative speedup model as real processors are added (I/O-model
    # derived: the wall-clock needs real hosts; the ledger is exact).
    n = 1 << 20
    v, k, omega_b = 32, 4, (2 * (n // 32) // 32) * 4
    mu = (n // v) * 4 * 4
    base = None
    for P in (1, 2, 4, 8):
        io = analysis.pems2_alltoallv_par_io_exact(v, P, k, mu, omega_b, 4096)
        t = io / P     # per-processor I/O time (fully parallel disks)
        base = base or t
        emit(f"psrs_model_speedup_P{P}", t, f"speedup={base / t:.2f}")


def run(smoke: bool | None = None) -> None:
    if smoke is None:
        smoke = os.environ.get("BENCH_FAST") == "1"
    rng = np.random.default_rng(0)
    v, k = 16, 4

    _figures(smoke, rng)

    if smoke:
        phase_n = 1 << 17
        pair_cfgs = ((1 << 17, 256),)
        stream_n, iters = 1 << 15, 3
    else:
        phase_n = 1 << 20
        pair_cfgs = ((1 << 17, 256), (1 << 19, 256), (1 << 19, 1024))
        stream_n, iters = 1 << 17, 5

    with tempfile.TemporaryDirectory() as td:
        phases = [_phase_rows(td, phase_n, v, k, rng)]
    p = phases[0]
    emit(f"psrs_phases_n{phase_n}", p["merge_s"] * 1e6,
         f"local_sort={p['local_sort_s']};network={p['network_s']};"
         f"merge={p['merge_s']};merge_dense={p['merge_dense_s']}")

    merge_rows = []
    for n, tile in pair_cfgs:
        row = _merge_pair_row(n, v, k, tile, rng, iters)
        merge_rows.append(row)
        emit(f"psrs_merge_pair_n{n}_t{tile}", row["kernel_ms"] * 1e3,
             f"dense_ms={row['dense_ms']};"
             f"speedup={row['speedup_vs_dense']}")

    stream_rows = []
    with tempfile.TemporaryDirectory() as td:
        for tier, driver in (("file", "explicit"), ("file", "async"),
                             ("memmap", "explicit")):
            row = _stream_row(td, stream_n, 8, 2, tier, driver, rng)
            stream_rows.append(row)
            emit(f"psrs_stream_{tier}_{driver}", row["wall_s"] * 1e6,
                 f"prefetch={row['merge_prefetch_events']};"
                 f"stall={row['merge_stall_s']}")

    with tempfile.TemporaryDirectory() as td:
        obs_row = _obs_row(td, stream_n, 8, 2, rng, iters)
    emit("psrs_obs_overhead", obs_row["traced_s"] * 1e6,
         f"plain_s={obs_row['plain_s']};"
         f"ratio={obs_row['overhead_ratio']}")

    out = {
        "benchmark": "psrs_phases",
        "backend": jax.default_backend(),
        "smoke": bool(smoke),
        "v": v,
        "note": ("phases: per-stage wall clock of one psrs_plan run "
                 "(min of 2 after warmup), grouped local_sort / network / "
                 "merge; merge_dense_s re-times the merge stage with "
                 "merge_kernel=False.  merge: paired kernel-vs-dense rows "
                 "on authentic post-alltoallv buckets — speedup_vs_dense "
                 "is the median per-iteration (dense / kernel) ratio, "
                 "interleaved in-process so machine speed cancels; the "
                 "regression gate floors it, so a silent fallback to the "
                 "dense path cannot read green.  stream: PSRS on a disk "
                 "backing; merge_prefetch_events counts bucket reads "
                 "submitted ahead of need while the previous round merged "
                 "(must stay nonzero).  obs: paired traced-vs-untraced "
                 "sort — overhead_ratio is the median per-iteration "
                 "(traced / untraced) ratio, gated by --obs-overhead; "
                 "the traced run's merged Perfetto trace is exported to "
                 "BENCH_psrs.trace.json."),
        "phases": phases,
        "merge": merge_rows,
        "stream": stream_rows,
        "obs": obs_row,
    }
    name = "BENCH_psrs.smoke.json" if smoke else "BENCH_psrs.json"
    with open(os.path.join(REPO_ROOT, name), "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")

    best = max(r["speedup_vs_dense"] for r in merge_rows)
    emit("psrs_merge_best_speedup", 0.0, f"speedup_vs_dense={best}")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run(smoke="--smoke" in sys.argv or None)
