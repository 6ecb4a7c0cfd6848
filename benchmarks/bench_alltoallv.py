"""Fig 7.2 analogue: single EM-Alltoallv call, PEMS1-indirect vs PEMS2-direct,
k ∈ {1, 4}: wall time + ledger I/O + the thesis' analytic times.

Direct mode is additionally measured both ways through the collective layer:

* ``direct`` (the default path) — fused word-level delivery: slice the send
  word range, deliver (transpose + fused counts/boundary handling), rebuild
  the store row with a concatenate the delivery fuses into.
* ``direct_dense`` — the seed implementation (``use_kernel=False``): dense
  field gather → transpose → whole-store dynamic-update-slice.

Both are timed with the identical protocol (fresh output buffer per call,
as the seed benchmark did), interleaved iteration-by-iteration so machine
noise hits both equally; the comparison is written to
``BENCH_alltoallv.json`` at the repo root.

A ``P = 2`` sweep rides along (rows tagged ``"P": 2``): the same paired
fused-vs-dense comparison through the mesh network phase — the
(src_proc, dst_proc)-tiled assembly route vs ``_global_transpose``'s dense
staging — in this process, over the first two devices, and skipped with a
notice where there are fewer.  On a CPU host two devices come from
``XLA_FLAGS=--xla_force_host_platform_device_count=2``, set before the
benchmark starts.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.core import ContextLayout, ContextStore, Pems, PemsConfig, analysis
from repro.launch.mesh import make_mesh_auto
from .common import emit

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V = 16

def _run_p2(sizes, iters, rounds):
    """The P=2 mesh sweep, in this process, over the first two devices.

    With fewer than two devices it is skipped with a notice: devices are
    never faked here, since a second process could not share an
    accelerator this one already holds.  On a CPU host, start the
    benchmark with ``XLA_FLAGS=--xla_force_host_platform_device_count=2``
    for two host devices."""
    devices = jax.devices()
    if len(devices) < 2:
        print(f"# P=2 sweep skipped: {len(devices)} {devices[0].platform} "
              "device(s), it needs 2", file=sys.stderr)
        return []
    P = 2
    mesh = make_mesh_auto((P,), ("vp",), devices=devices[:P])
    rows = []
    for n_words in sizes:
        omega = n_words // (V * V)
        lo = (ContextLayout()
              .add("send", (V, omega), jnp.int32)
              .add("recv", (V, omega), jnp.int32))
        pems = Pems(PemsConfig(v=V, k=1, P=P), lo, mesh=mesh)
        store = pems.init()
        tf, td = [], []
        for _ in range(rounds):
            @jax.jit
            def fused_call(data):
                st = ContextStore(lo, data)
                return pems.alltoallv(st, "send", "recv", mode="direct").data

            @jax.jit
            def dense_call(data):
                st = ContextStore(lo, data)
                return pems.alltoallv(st, "send", "recv", mode="direct",
                                      use_kernel=False).data

            data = jnp.array(store.data)
            f, d = _interleaved_times(fused_call, dense_call, data, iters)
            tf.extend(f)
            td.extend(d)
        ratios = sorted(d / f for f, d in zip(tf, td))
        tf.sort()
        td.sort()
        # Ledger figures from a fresh executor and exactly one call — the
        # timing pems above accrues events at every retrace of both modes.
        led = Pems(PemsConfig(v=V, k=1, P=P), lo, mesh=mesh)
        led.alltoallv(led.init(), "send", "recv", mode="direct")
        rows.append({
            "v": V,
            "P": P,
            "omega": omega,
            "n_words": n_words,
            "direct_us": round(tf[len(tf) // 2] * 1e6, 1),
            "direct_min_us": round(tf[0] * 1e6, 1),
            "direct_dense_us": round(td[len(td) // 2] * 1e6, 1),
            "direct_dense_min_us": round(td[0] * 1e6, 1),
            "speedup_vs_dense": round(ratios[len(ratios) // 2], 3),
            "speedup_vs_dense_of_medians": round(
                td[len(td) // 2] / tf[len(tf) // 2], 3),
            "speedup_vs_dense_min": round(td[0] / tf[0], 3),
            "io_bytes_direct_k1": led.ledger.io_total,
            "network_bytes": led.ledger.network,
        })
    return rows


def _interleaved_times(fused_fn, dense_fn, data, iters):
    """Time both paths back-to-back per iteration (identical protocol);
    returns paired (unsorted) seconds lists — consecutive samples share the
    machine state, so per-pair ratios cancel load drift."""
    jax.block_until_ready(fused_fn(data))                    # compile + warm
    jax.block_until_ready(dense_fn(data))
    tf, td = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fused_fn(data))
        tf.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(dense_fn(data))
        td.append(time.perf_counter() - t0)
    return tf, td


def run(smoke: bool | None = None) -> None:
    if smoke is None:
        smoke = os.environ.get("BENCH_FAST") == "1"
    sizes = (1 << 14, 1 << 16) if smoke else (1 << 14, 1 << 16, 1 << 18, 1 << 20)

    model = analysis.MachineModel(B=4096, S=1.0, G=1.0)
    configs = []
    for n_words in sizes:
        omega = n_words // (V * V)
        # Cheap configs get more samples: this box is noisy, and the robust
        # estimators below (paired-ratio and pooled medians) sharpen with
        # sample count.  Several rounds with fresh buffers/executables guard
        # against one unlucky allocation alignment dominating a process.
        iters = 6 if smoke else (100 if n_words <= 1 << 16 else 40)
        rounds = 1 if smoke else 3
        lo = (ContextLayout()
              .add("send", (V, omega), jnp.int32)
              .add("recv", (V, omega), jnp.int32))

        pems = Pems(PemsConfig(v=V, k=1), lo)
        store = pems.init()

        tf, td = [], []                        # all rounds' samples, pooled
        for _ in range(rounds):
            @jax.jit
            def fused_call(data):
                st = ContextStore(lo, data)
                st = pems.alltoallv(st, "send", "recv", mode="direct")
                return st.data

            @jax.jit
            def dense_call(data):
                st = ContextStore(lo, data)
                st = pems.alltoallv(st, "send", "recv", mode="direct",
                                    use_kernel=False)
                return st.data

            data = jnp.array(store.data)         # fresh buffer per round
            f, d = _interleaved_times(fused_call, dense_call, data, iters)
            tf.extend(f)
            td.extend(d)
        # Paired per-iteration ratios: the robust A/B statistic on a noisy
        # box (each pair ran back-to-back under the same machine state).
        ratios = sorted(d / f for f, d in zip(tf, td))
        tf.sort()
        td.sort()

        @jax.jit
        def indirect_call(data):
            st = ContextStore(lo, data)
            st = pems.alltoallv(st, "send", "recv", mode="indirect")
            return st.data

        # Same protocol as the direct paths (one warm call, then the same
        # sample count, median) so the Fig 7.2 direct-vs-indirect comparison
        # is not distorted by asymmetric sampling.
        jax.block_until_ready(indirect_call(store.data))
        ti = []
        for _ in range(iters * rounds):
            t0 = time.perf_counter()
            jax.block_until_ready(indirect_call(store.data))
            ti.append(time.perf_counter() - t0)
        ti.sort()
        # Median of the pooled interleaved samples as the primary statistic
        # (robust to load spikes on a shared box); mins reported alongside.
        us_fused = tf[len(tf) // 2] * 1e6
        us_dense = td[len(td) // 2] * 1e6
        us_indirect = ti[len(ti) // 2] * 1e6

        row = {
            "v": V,
            "P": 1,
            "omega": omega,
            "n_words": n_words,
            "direct_us": round(us_fused, 1),
            "direct_min_us": round(tf[0] * 1e6, 1),
            "direct_dense_us": round(us_dense, 1),
            "direct_dense_min_us": round(td[0] * 1e6, 1),
            "indirect_us": round(us_indirect, 1),
            "speedup_vs_dense": round(ratios[len(ratios) // 2], 3),
            "speedup_vs_dense_of_medians": round(us_dense / us_fused, 3),
            "speedup_vs_dense_min": round(td[0] / tf[0], 3),
        }

        for k in (1, 4):
            for mode in ("direct", "indirect"):
                base = Pems(PemsConfig(v=V, k=k), lo)
                st2 = base.init()
                base.alltoallv(st2, "send", "recv", mode=mode)
                io = base.ledger.io_total
                if mode == "direct":
                    t_model = analysis.pems2_alltoallv_seq_time(
                        V, k, lo.live_bytes, omega * 4, model)
                    us = us_fused
                else:
                    t_model = analysis.pems1_alltoallv_time(
                        V, lo.live_bytes, omega * 4, model)
                    us = us_indirect
                emit(f"alltoallv_{mode}_n{n_words}_k{k}", us,
                     f"io_bytes={io};model_time_blocks={t_model:.0f}")
                row[f"io_bytes_{mode}_k{k}"] = io
        configs.append(row)

    # P = 2 mesh sweep (fused assembly route vs dense staging).
    p2_sizes = sizes[:2] if smoke else sizes
    p2_iters = 6 if smoke else 40
    p2_rounds = 1 if smoke else 3
    for row in _run_p2(p2_sizes, p2_iters, p2_rounds):
        emit(f"alltoallv_direct_P2_n{row['n_words']}_k1", row["direct_us"],
             f"speedup_vs_dense={row['speedup_vs_dense']}")
        configs.append(row)

    out = {
        "benchmark": "alltoallv_direct_delivery",
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "v": V,
        "smoke": bool(smoke),
        "note": ("direct_us is the fused word-level kernel path; "
                 "direct_dense_us is the seed dense-transpose implementation "
                 "measured with the identical protocol, interleaved in the "
                 "same process; P=2 rows run the mesh network phase on the "
                 "first two devices"),
        "configs": configs,
    }
    # Smoke runs write to a separate file so CI / BENCH_FAST sweeps never
    # clobber the full-sweep deliverable at the repo root.
    name = "BENCH_alltoallv.smoke.json" if smoke else "BENCH_alltoallv.json"
    with open(os.path.join(REPO_ROOT, name), "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sweep for CI")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(smoke=args.smoke or None)
