"""What a cell is made of, found by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration
and traffic mix, and each metric.  Everything that belongs to one of them
lives in a file of its own under a search root (``bench/`` by default):

* the configuration's ``file`` (``configs/<config>.json``): the
  configuration as it is run, with the deployment's data scale under
  ``data``;
* ``traffic/<traffic>.json``: the parameters of a traffic mix, read, with
  the configuration's ``data`` laid over them, by the generator it names;
* ``generators/<generator>.py``: ``generate(params, seed, index)``;
* ``apps/<app>.py``: ``run_job``, ``reference`` and ``control`` of the
  system entry a configuration drives;
* ``metrics/<metric>.py``: ``read(run) -> float | None``.

So a later cell, generator or metric is added with new files and new
entries only; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the Python file at ``path`` (its name may hold dots)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_plugin_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict          # the mix, with the configuration's ``data`` over it
    app: object
    generator: object
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)
    readers: dict = field(default_factory=dict)      # metric name -> module


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(bench: dict, name: str, search=(BENCH_DIR,)) -> Cell:
    """The cell ``name`` of the parsed ``BENCHMARK.json`` ``bench``.  Each
    file is looked up under every root of ``search`` in turn."""
    def find(sub: str, fname: str) -> str:
        for d in search:
            p = os.path.join(d, sub, fname)
            if os.path.isfile(p):
                return p
        raise FileNotFoundError(
            f"{sub}/{fname} is under none of {list(search)}")

    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r} (have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    # ``file`` is relative to the checkout's root (or absolute).
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(find("traffic", w["traffic"] + ".json"))
    traffic.update(config.get("data", {}))
    app = load_module(find("apps", config["app"] + ".py"), config["app"])
    gen = load_module(find("generators", traffic["generator"] + ".py"),
                      traffic["generator"])
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    readers = {m["name"]: load_module(find("metrics", m["name"] + ".py"),
                                      m["name"])
               for m in e2e + per_layer}
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic=traffic,
                app=app, generator=gen, end_to_end=e2e, per_layer=per_layer,
                readers=readers)
