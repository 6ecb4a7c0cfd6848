"""BENCHMARK.json, and the files each of its cells is found by."""

import json
import os
import re

import pytest

from bench import spec

BENCH = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")


def test_benchmark_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][0] == "python3"
    assert os.path.isfile(os.path.join(spec.ROOT, BENCH["command"][1]))
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_and_metrics_are_well_formed():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    for n in names:
        assert NAME.fullmatch(n), n
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_finds_its_files_by_name(workload):
    cell = spec.load_cell(BENCH, workload)
    assert cell.config["app"] == "psrs_sort"
    assert callable(cell.app.run_job) and callable(cell.app.reference)
    assert callable(cell.generator.generate)
    assert "setup_s" in cell.readers and "sort_rate" in cell.readers
    assert cell.per_layer, "every cell reports a per-layer metric"
    for name, mod in cell.readers.items():
        assert callable(mod.read), name


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_config_file_is_under_paths_and_names_its_kernels(workload):
    """The configuration's file lies under ``paths``, its data scale reaches
    the generator, and each ``<kernel>_roofline`` metric of the cell names
    a Pallas kernel of the program."""
    cell = spec.load_cell(BENCH, workload)
    entry = {c["name"]: c for c in BENCH["configs"]}[cell.config_name]
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    assert cell.config["call"]["v"] == 16
    for key, val in cell.config["data"].items():
        assert cell.traffic[key] == val
    for key in entry["reduced"]:
        assert cell.config[key] != cell.config["reduced_from"][key]
    kernels = [m["name"][: -len("_roofline")] for m in cell.per_layer
               if m["name"].endswith("_roofline")]
    assert kernels
    for k in kernels:
        assert os.path.isdir(os.path.join(spec.ROOT, "src", "repro",
                                          "kernels", k)), k


def test_a_cell_that_exists_only_in_a_test_directory_loads():
    """A later cell, generator and metric are new files and entries only."""
    bench = spec.load_json(os.path.join(FIXTURES, "bench.json"))
    cell = spec.load_cell(bench, "ramp.device",
                          search=(FIXTURES, spec.BENCH_DIR))
    assert cell.traffic["generator"] == "ramp"
    keys = cell.generator.generate(cell.traffic, 5, 0)
    assert keys.size == 1024 and keys.dtype.name == "int32"
    assert [m["name"] for m in cell.end_to_end] == [
        "sort_rate", "setup_s", "jobs_run"]
    assert cell.readers["jobs_run"].read(type("R", (), {"jobs": [1, 2]})) == 2


def test_unknown_names_fail_loudly():
    with pytest.raises(KeyError):
        spec.load_cell(BENCH, "no_such_cell")
    bench = spec.load_json(os.path.join(FIXTURES, "bench.json"))
    bench["end_to_end"].append({"name": "no_such_metric", "unit": "s",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock"})
    with pytest.raises(FileNotFoundError):
        spec.load_cell(bench, "tiny.device", search=(FIXTURES,
                                                     spec.BENCH_DIR))
