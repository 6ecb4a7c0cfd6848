#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload is_a.device --seed 7 --seconds 20 --trace 0

from the root of a checkout that holds the program under ``src/``.  The
cell (``BENCHMARK.json``'s workload) names a configuration and a traffic
mix; both, the generator, the application adapter and each metric's reader
are files found by name (:mod:`bench.spec`).

A run: find the chips (no TPU, or fewer chips than the cell asks for:
exit 1 with no result), make the cell's key sets from ``--seed``, and run
one warm-up job on the first of them, with the persistent compile cache in
``JAX_COMPILATION_CACHE_DIR`` where that is set and in
``<checkout>/.jax_cache`` otherwise.  Then measure, on the other key sets
in turn.  With ``--trace 0`` whole jobs run back to back for ``--seconds``
(:mod:`bench.window`) and the end-to-end metrics are printed; with
``--trace 1`` one job runs under ``jax.profiler`` and the per-layer
metrics, the device's busy time and a breakdown are printed.  Then every
job's output is checked against the plain reference (:mod:`bench.check`).
The numbers compared, each beside its limit, are the last lines of
standard error and the last key of the result line, which is the last line
of standard output.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    # Run as a script, the harness's own directory would come first on the
    # path; the checkout's root takes its place.
    sys.path[0] = ROOT

from bench import check, devtrace, spec, window  # noqa: E402
from bench.roofline import peaks  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed)")
    return ap.parse_args(argv)


def keep_logs_in_tmpdir() -> None:
    """Send the TPU runtime's log files under ``TMPDIR`` (its default is a
    fixed ``/tmp`` path); an explicit ``TPU_LOG_DIR`` is kept.  Call before
    JAX is imported."""
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))


def use_program(root: str = ROOT) -> None:
    """Import the program from the checkout's own ``src/``, never from
    anywhere else on the path."""
    src = os.path.join(root, "src")
    if not os.path.isfile(os.path.join(src, "repro", "pems_apps", "psrs.py")):
        raise FileNotFoundError(f"no program at {src}/repro")
    sys.path.insert(0, src)
    from repro.pems_apps import psrs
    if not os.path.abspath(psrs.__file__).startswith(src + os.sep):
        raise ImportError(f"repro imported from {psrs.__file__}, not {src}")


def acquire_devices(chips: int):
    """The first ``chips`` TPU devices; :class:`NoChip` otherwise.  Turns
    on the persistent compile cache, in ``JAX_COMPILATION_CACHE_DIR`` where
    that is set and at its fixed path in the checkout otherwise, and caches
    every program, however short its compile."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX has {len(devs)}")
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devs[:chips]


class CompileWatch:
    """Counts JAX's compiles and cache hits, and keeps the wall-clock
    spans of its tracing, lowering and compile stages, while active."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "jax: trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax: lower",
              "/jax/core/compile/backend_compile_duration":
                  "jax: compile or cache load"}

    def __init__(self):
        self.requests = 0
        self.hits = 0
        self.spans = []          # (label, wall t0, wall t1)
        self.seconds = {}

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_span(self, event, start, end, **_):
        label = self.EVENTS.get(event)
        if label is not None:
            self.spans.append((label, start, end))
            self.seconds[label] = self.seconds.get(label, 0.0) + end - start
            if event.endswith("backend_compile_duration"):
                self.requests += 1

    @property
    def compiles(self) -> int:
        return self.requests - self.hits

    def __enter__(self):
        import jax.monitoring as mon
        mon.register_event_listener(self._on_event)
        mon.register_event_time_span_listener(self._on_span)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_listener(self._on_event)
        mon.unregister_event_time_span_listener(self._on_span)
        return False

    def summary(self) -> str:
        secs = ", ".join(f"{k} {v:.3f} s" for k, v in
                         sorted(self.seconds.items()))
        return (f"compiles {self.compiles}, cache loads {self.hits}"
                + (f"; {secs}" if secs else ""))


class Run:
    """What a metric's reader reads: the cell, the set-up time, the jobs
    measured (the window's, or the one traced job) and, in a traced run,
    the trace with the bounds of the traced job on its clock."""

    def __init__(self, cell, setup_s, jobs, device_kind, profile=None,
                 window_ns=None):
        self.cell = cell
        self.setup_s = setup_s
        self.jobs = jobs
        self.device_kind = device_kind
        self.profile = profile
        self.window_ns = window_ns

    @property
    def peaks(self) -> dict:
        return peaks(self.device_kind)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _traced_job(job, key_sets, trace_dir):
    """One job under the profiler.  Returns the job, its output, the
    profile, the job's bounds on the trace's clock and the host spans of
    JAX's compile stages and of the program's tracer put on that clock."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # Python call tracing slows the host
    opts.enable_hlo_proto = False
    with CompileWatch() as cw:
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            wall0, perf0 = time.time(), time.perf_counter()
            with jax.profiler.TraceAnnotation(devtrace.JOB_SPAN):
                jobs, outputs = window.run_window(job, key_sets, 0,
                                                  max_jobs=1)
        finally:
            jax.profiler.stop_trace()
    _log(f"traced job: {cw.summary()}")
    prof = devtrace.load(trace_dir)
    lo, hi = prof.window()
    spans = [(lbl, lo + (t0 - wall0) * 1e9, lo + (t1 - wall0) * 1e9)
             for lbl, t0, t1 in cw.spans]
    spans += [(nm, lo + (t0 - perf0) * 1e9, lo + (t1 - perf0) * 1e9)
              for nm, t0, t1 in jobs[0].counters.pop("spans", ())]
    return jobs, outputs, prof, (lo, hi), spans


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float = T_START, trace_dir=None):
    """Set up, measure and check one cell on ``devices``.  Returns the
    result object (without printing it)."""
    import jax

    dev = devices[0]
    if dev.platform == "tpu":
        peaks(dev.device_kind)            # an unknown chip fails up front

    def job(keys):
        return cell.app.run_job(keys, cell.config, traced=trace)

    n_sets = int(cell.traffic["key_sets"])
    if n_sets < 2:
        raise ValueError("a mix needs a key set for the warm-up and one or "
                         f"more for the window, not {n_sets}")
    key_sets = [cell.generator.generate(cell.traffic, seed, i)
                for i in range(n_sets)]
    _log(f"setup: {n_sets} key sets of {key_sets[0].size} keys, "
         f"{time.perf_counter() - t_start:.3f} s in")

    # Warm-up: one job at the cell's shapes on key set 0, which the
    # measured jobs never sort.
    with CompileWatch() as cw:
        t0 = time.perf_counter()
        warm, warm_counters = job(key_sets[0])
        del warm
        warm_s = time.perf_counter() - t0
    _log(f"setup: warm-up job {warm_s:.3f} s, {cw.summary()}")
    measured = key_sets[1:]

    profile = window_ns = None
    spans = ()
    setup_s = time.perf_counter() - t_start
    if trace:
        with tempfile.TemporaryDirectory() as td:
            jobs, outputs, profile, window_ns, spans = _traced_job(
                job, measured, trace_dir or td)
    else:
        with CompileWatch() as cw:
            jobs, outputs = window.run_window(job, measured, seconds)
        _log(f"window: {len(jobs)} jobs in "
             f"{jobs[-1].t1 - jobs[0].t0:.3f} s, {cw.summary()}")
    _log("jobs: wall s " + " ".join(f"{j.wall_s:.3f}" for j in jobs[:12])
         + (f" ... ({len(jobs)} jobs)" if len(jobs) > 12 else ""))

    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    if "disk_write_bytes" in warm_counters:
        written = sum(c.get("disk_write_bytes", 0) for c in
                      [warm_counters] + [j.counters for j in jobs])
        _log(f"disk: {written} B written to backing files in this run, "
             f"under {tempfile.gettempdir()}")

    run = Run(cell, setup_s, jobs, dev.device_kind, profile=profile,
              window_ns=window_ns)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        val = cell.readers[m["name"]].read(run)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    result = {"correct": None, "attempted": len(jobs), "failed": None,
              "metrics": metrics, "device": device}
    if trace:
        lo, hi = window_ns
        device["busy_s"] = devtrace.busy_s(profile, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": devtrace.top_ops(profile, lo, hi),
            "idle_gaps": devtrace.idle_gaps(profile, lo, hi, spans)}

    # The check, after the window, with the device's peak read.
    verdict = check.judge(outputs, [j.key_set for j in jobs], measured,
                          cell.app.reference)
    result["correct"] = verdict["correct"]
    result["failed"] = verdict["failed"]
    result["check"] = verdict["numbers"]
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    keep_logs_in_tmpdir()
    try:
        bench = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cell = spec.load_cell(bench, args.workload)
        use_program()
        devices = acquire_devices(cell.chips)
    except (OSError, ImportError, KeyError, NoChip) as e:
        _log(f"bench: cannot run {args.workload}: {e}")
        return 1
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, trace_dir=args.trace_dir)
    for name, num in result["check"].items():
        _log(f"check: {name} {num['value']} limit {num['limit']}")
    _log(f"check: correct {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
