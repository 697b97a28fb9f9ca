"""Find a cell's configuration, traffic mix, driver and metric readers by
name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own:

* ``BENCHMARK.json`` names the cells, the configurations' files and the
  metrics;
* ``bench/traffic/<traffic>.json`` holds a mix's parameters and names the
  driver that reads them (``bench/drivers/<driver>.py``);
* ``bench/metrics/<metric>.py`` reads one per-layer metric;
* ``bench/references/<reference>.py`` is a configuration's plain reference.

So a later change adds a cell, a mix or a metric by adding files and
entries, and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(RuntimeError):
    """A cell, configuration, mix or metric that cannot be found."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic file's contents
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path (file names may hold dots)."""
    if not os.path.isfile(path):
        raise SpecError(f"no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str) -> bool:
    wl = metric.get("workloads")
    return wl is None or cell in wl


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names no configuration "
                        f"{w['config']!r}")
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def driver(name: str, root: str = ROOT):
    return load_module(os.path.join(root, "bench", "drivers", name + ".py"),
                       f"bench_driver_{name}")


def reference(name: str, root: str = ROOT):
    return load_module(
        os.path.join(root, "bench", "references", name + ".py"),
        f"bench_reference_{name}")


def metric_reader(name: str, root: str = ROOT):
    """``read(ctx) -> float | None`` of ``bench/metrics/<name>.py``."""
    mod = load_module(os.path.join(root, "bench", "metrics", name + ".py"),
                      "bench_metric_" + name.replace(".", "_"))
    return mod.read
