"""One sort job through the public entry ``repro.pems_apps.psrs.psrs_sort``,
with the plain reference it is checked against and the control that the
check must refuse.

A job starts from numpy keys on the host and ends with sorted numpy keys on
the host, as a user's call does: the entry's own tracing, lowering and
compile-cache lookup, the transfers and the extraction are all inside it.
"""

from __future__ import annotations

import os
import tempfile

import numpy as np


def run_job(keys: np.ndarray, config: dict, traced: bool = False):
    """Sort ``keys`` with the configuration's ``call`` arguments.  Returns
    the sorted keys and the job's counters: on disk tiers the backing's
    allocated bytes (read before it is deleted), the I/O ledger's disk
    bytes, the executor's stall seconds and, in a ``traced`` job, the
    seconds of each plan stage from the program's own span tracer.

    The device tier never turns that tracer on: it would run the eager
    program in place of the jitted one.  On the other tiers it only adds
    host spans around stage bodies that are jitted either way."""
    from repro.pems_apps import psrs

    call = dict(config["call"])
    if call.get("tier", "device") == "device":
        return psrs.psrs_sort(keys, **call), {}
    # A fresh backing file per job, in a directory removed with it.
    with tempfile.TemporaryDirectory(prefix="bench_ctx_") as td:
        out, pems = psrs.psrs_sort(
            keys, backing_path=os.path.join(td, "ctx.bin"),
            return_pems=True, trace=traced, **call)
        pems.backing.close()
        space = sum(os.stat(os.path.join(td, f)).st_blocks * 512
                    for f in os.listdir(td))
    led = pems.merged_shard_ledger()
    counters = {
        "disk_space_bytes": space,
        "disk_read_bytes": led.disk_read_bytes,
        "disk_write_bytes": led.disk_write_bytes,
        "stall_s": pems.merged_shard_stats().stall_s,
    }
    if traced:
        stages, spans = {}, []
        tracers = {id(t): t for t in [pems.tracer, *pems.shard_tracers]}
        for tr in tracers.values():
            for ph, name, _tid, ts, dur, cat, _args in tr.events():
                if ph != "X":
                    continue
                spans.append(("program: " + name, tr.epoch + ts,
                              tr.epoch + ts + dur))
                if cat == "stage":
                    stage = name.split(":", 1)[1]
                    stages[stage] = stages.get(stage, 0.0) + dur
        counters["stage_s"] = stages
        counters["spans"] = spans       # on the host's perf_counter clock
    return out, counters


def reference(keys: np.ndarray) -> np.ndarray:
    """The plain reference: numpy's sort of the same keys."""
    return np.sort(keys, kind="stable")


def control(keys: np.ndarray) -> np.ndarray:
    """The reference one precision down: keys ordered by their bfloat16
    rounding (8 significant bits), as a sort that packs keys into fewer
    bits would order them.  A permutation of the input, sorted to within
    bfloat16, not exactly."""
    import ml_dtypes

    coarse = keys.astype(np.float32).astype(ml_dtypes.bfloat16)
    return keys[np.argsort(coarse.astype(np.float32), kind="stable")]
