"""What one cell is, read from ``BENCHMARK.json`` and the files it names.

Everything a cell needs is found by name: its configuration file (the
``file`` of its ``configs`` entry), its traffic mix
(``bench/traffic/<traffic>.json``), the adapter and reference of the
configuration's ``model_type`` (``bench/adapters/<model_type>.py``,
``bench/reference/<model_type>.py``), the harness of the mix's ``kind``
(``bench/harness/<kind>.py``) and one reader per metric
(``bench/metrics/<metric>.py``).  Adding a cell, a configuration, a mix
or a metric adds files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    conf: dict            # the configuration file, as run
    traffic: dict         # the traffic mix file
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_json: str = None) -> Cell:
    bench = load_json(bench_json or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    conf = load_json(os.path.join(ROOT, cfg["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config_name=cfg["name"],
                conf=conf, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def adapter(conf: dict):
    return importlib.import_module(f"bench.adapters.{conf['model_type']}")


def reference(conf: dict):
    return importlib.import_module(f"bench.reference.{conf['model_type']}")


def harness(traffic: dict):
    return importlib.import_module(f"bench.harness.{traffic['kind']}")


def metric_reader(name: str):
    """``bench/metrics/<name>.py`` (a name may hold dots, so the file is
    loaded by path)."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "__"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
