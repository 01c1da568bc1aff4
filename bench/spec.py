"""What a run measures, found by name from ``BENCHMARK.json``.

Every configuration, traffic mix and metric lives in a file of its own:

* ``bench/configs/<config>.json``: the configuration as it is run;
* ``bench/traffic/<traffic>.json``: the parameters the one load
  generator (``load.py``) reads;
* ``bench/metrics/<metric>.py``: a reader, ``read(run) -> float | None``,
  for each end-to-end and per-layer metric;
* ``bench/limits/<cell>.json``: the limit of each number ``check.py``
  compares in that cell (null: reported, not compared).

So a later change adds a cell, a mix or a metric by adding files and
``BENCHMARK.json`` entries, and edits nothing that exists.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
HERE = os.path.basename(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    root: str               # the checkout the files were found in
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple       # metric entries of BENCHMARK.json
    per_layer: tuple
    limits: dict


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    """A metric with ``workloads`` is reported in those cells; one
    without it in every cell that reports what it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in e2e_names


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, HERE, "traffic",
                                      f"{w['traffic']}.json"))
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name, ()))
    names = {m["name"] for m in e2e}
    layer = tuple(m for m in bench["per_layer"] if _reports(m, name, names))
    limits = _load_json(os.path.join(root, HERE, "limits", f"{name}.json"))
    return Cell(root=root, name=name, config=config, traffic=traffic,
                chips=int(w["chips"]), end_to_end=e2e, per_layer=layer,
                limits=limits)


def reader(metric: str, root: str = ROOT):
    """``read`` of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, HERE, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family(config: dict, kind: str):
    """``bench/<kind>/<family>.py`` for a configuration (``kind`` is
    ``reference`` or ``flops``)."""
    return importlib.import_module(f"bench.{kind}.{config['family']}")


def peaks(device_kind: str) -> dict:
    table = _load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]
