#!/usr/bin/env python3
"""Run a cell with the control in the program's place, to show that the
check refuses it.

    python3 bench/control.py --workload is_a.device --seeds 11 12 13 --seconds 5

For each seed the cell is set up and measured as ``bench/run.py`` does it,
with the application's ``control`` (its reference one precision down)
answering every job instead of the program, and the numbers the check
compared are printed as one JSON line per seed.  Benchmark runs never run
this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench import run, spec  # noqa: E402


class ControlApp:
    """The application ``app`` with its control answering every job."""

    def __init__(self, app):
        self.reference = app.reference
        self.control = app.control

    def run_job(self, keys, config, traced=False):
        return self.control(keys), {}


def control_cell(cell):
    """A copy of ``cell`` whose jobs the control answers."""
    import copy

    cc = copy.copy(cell)
    cc.app = ControlApp(cell.app)
    return cc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    run.keep_logs_in_tmpdir()
    try:
        bench = spec.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
        cell = control_cell(spec.load_cell(bench, args.workload))
        run.use_program()
        devices = run.acquire_devices(cell.chips)
    except (OSError, ImportError, KeyError, run.NoChip) as e:
        print(f"control: cannot run {args.workload}: {e}", file=sys.stderr)
        return 1
    for seed in args.seeds:
        res = run.run_cell(cell, seed, args.seconds, False, devices,
                           t_start=time.perf_counter())
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "check": res["check"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
