"""A whole run on the CPU at a tiny size, with the look for a chip skipped:
the result line, the refusal without a chip, and the check refusing the
control and each fault the cells can have."""

import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import control, run, spec

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
TEST_BENCH = spec.load_json(os.path.join(FIXTURES, "bench.json"))
SEED = 2**31 + 77


def _cell(name):
    run.use_program()
    return spec.load_cell(TEST_BENCH, name, search=(FIXTURES,
                                                    spec.BENCH_DIR))


def _run(cell, trace=False):
    return run.run_cell(cell, SEED, 0.5, trace, jax.devices()[:1])


@pytest.mark.parametrize("name", ["tiny.device", "tiny.file"])
def test_a_run_checks_every_job_and_reports_its_metrics(name):
    cell = _cell(name)
    res = _run(cell)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["check"]["mismatched_keys"] == {"value": 0, "limit": 0}
    assert list(res)[-1] == "check"
    assert res["metrics"]["sort_rate"]["unit"] == "Mkeys/s"
    assert res["metrics"]["sort_rate"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    json.dumps(res)
    if name == "tiny.file":
        # v=4: (2v + 3) words of 4 bytes per key, all written.
        assert res["metrics"]["disk_space_per_key"]["value"] >= 44


def test_a_traced_file_run_reads_the_programs_counters_and_spans():
    res = _run(_cell("tiny.file"), trace=True)
    assert res["correct"] is True and res["attempted"] == 1
    m = res["metrics"]
    assert set(m) == {"swap_stall_share", "disk_bytes_per_key",
                      "stage_share.alltoallv"}
    assert 0 < m["stage_share.alltoallv"]["value"] < 100
    assert 0 <= m["swap_stall_share"]["value"] < 100
    assert m["disk_bytes_per_key"]["value"] > 44
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_window_never_re_sorts_the_warm_ups_keys():
    cell = _cell("tiny.device")
    seen = []

    class Recorder:
        reference = staticmethod(cell.app.reference)

        def run_job(self, keys, config, traced=False):
            seen.append(keys.tobytes())
            return np.sort(keys), {}

    cell.app = Recorder()
    res = run.run_cell(cell, SEED, 0.01, False, jax.devices()[:1])
    assert res["correct"] is True
    warm, measured = seen[0], seen[1:]
    assert len(measured) == res["attempted"] >= 1
    assert warm not in measured
    # Three key sets: the warm-up's, then the other two in turn.
    assert len(set(measured)) == min(2, len(measured))
    assert measured[2:] == [measured[i % 2] for i in range(2, len(measured))]


def _fault_state_unchanged(monkeypatch):
    from repro.pems_apps import psrs
    monkeypatch.setattr(psrs, "psrs_sort",
                        lambda keys, **kw: np.array(keys))


def _fault_half_left_out(monkeypatch):
    from repro.pems_apps import psrs
    real = psrs.psrs_sort
    monkeypatch.setattr(psrs, "psrs_sort", lambda keys, **kw: real(
        keys[: keys.size // 2], **kw))


def _fault_exchange_left_out(monkeypatch):
    from repro.core.executor import Pems
    monkeypatch.setattr(Pems, "alltoallv",
                        lambda self, store, *a, **kw: store)


def _fault_answer_altered(monkeypatch):
    from repro.pems_apps import psrs
    real = psrs.psrs_sort

    def altered(keys, **kw):
        out = np.array(real(keys, **kw))
        out[out.size // 2] += 1
        return out
    monkeypatch.setattr(psrs, "psrs_sort", altered)


FAULTS = {"state_unchanged": _fault_state_unchanged,
          "half_left_out": _fault_half_left_out,
          "exchange_left_out": _fault_exchange_left_out,
          "answer_altered": _fault_answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_check_refuses_each_fault(fault, monkeypatch):
    cell = _cell("tiny.device")
    FAULTS[fault](monkeypatch)
    res = _run(cell)
    assert res["correct"] is False
    assert res["failed"] >= 1
    assert res["check"]["mismatched_keys"]["value"] > 0


def test_the_check_refuses_the_control():
    res = _run(control.control_cell(_cell("tiny.device")))
    assert res["correct"] is False
    assert res["check"]["mismatched_keys"]["value"] > 0


def test_the_control_is_a_permutation_sorted_to_bfloat16():
    app = _cell("tiny.device").app
    keys = np.random.default_rng(0).integers(0, 1 << 19, 4096, np.int32)
    out = app.control(keys)
    assert np.array_equal(np.sort(out), np.sort(keys))
    assert not np.array_equal(out, np.sort(keys))


def _cli(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_without_a_chip_the_run_fails_and_prints_no_result():
    r = _cli(["--workload", "is_a.device", "--seed", "1", "--seconds", "1"],
             spec.ROOT)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "no TPU" in r.stderr


def test_without_the_program_the_run_fails_and_prints_no_result(tmp_path):
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(spec.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(["--workload", "is_a.device", "--seed", "1", "--seconds", "1"],
             tmp_path)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "no program" in r.stderr
