"""Find a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix, and
each metric. Their files sit at fixed places under ``bench/``, so a later
change adds a configuration, a mix or a per-layer metric as new files and
edits none that is there:

* ``bench/configs/<config>.json``        -- the deployment;
* ``bench/traffic/<traffic>.json``       -- the mix, read by ``bench.traffic``;
* ``bench/layer_metrics/<metric>.py``    -- ``read(run) -> float | None``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: str | None = None          # per-layer metrics only
    workloads: tuple | None = None    # the cells that report it
    reader: object = None             # per-layer metrics: read(run)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple                 # Metric, reported with --trace 0
    per_layer: tuple                  # Metric, reported with --trace 1


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(root: str, name: str):
    """The ``read`` function of ``bench/layer_metrics/<name>.py``."""
    path = os.path.join(root, "bench", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_layer_metric_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _applies(entry: dict, cell: str, reports: set) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves") is None or entry["moves"] in reports


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Resolve the workload `name` of ``<root>/BENCHMARK.json``."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(cells: {sorted(by_name)})")
    w = by_name[name]
    config = _load_json(os.path.join(root, "bench", "configs",
                                     f"{w['config']}.json"))
    traffic = _load_json(os.path.join(root, "bench", "traffic",
                                      f"{w['traffic']}.json"))
    e2e = tuple(Metric(m["name"], m["unit"], m["better"], m["source"],
                       workloads=tuple(m["workloads"]) if "workloads" in m
                       else None)
                for m in bench["end_to_end"] if _applies(m, name, set()))
    reports = {m.name for m in e2e}
    per_layer = tuple(
        Metric(m["name"], m["unit"], m["better"], m["source"], m["moves"],
               tuple(m["workloads"]) if "workloads" in m else None,
               load_reader(root, m["name"]))
        for m in bench["per_layer"] if _applies(m, name, reports))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def find_cell(name: str, root: str = ROOT) -> Cell:
    """The cell `name` as ``BENCHMARK.json`` lists it, or else, for a cell
    ``<config>.<traffic>`` that it does not list yet, one built from those
    two files alone, with no metrics (for the sweep and the proof, which
    read a cell's numbers before it is listed)."""
    try:
        return load_cell(name, root)
    except KeyError:
        if "." not in name:
            raise
    config, traffic = name.split(".", 1)
    cfg = _load_json(os.path.join(root, "bench", "configs", f"{config}.json"))
    mix = _load_json(os.path.join(root, "bench", "traffic", f"{traffic}.json"))
    return Cell(name=name, chips=int(cfg["chips"]), config=cfg, traffic=mix,
                end_to_end=(), per_layer=())

