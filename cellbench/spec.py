"""Finding a cell's files by the names ``BENCHMARK.json`` gives.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix. The configuration's file is the one its ``configs`` entry names;
the traffic mix is ``cellbench/traffic/<traffic>.json``; a per-layer
metric ``<name>`` is read by ``cellbench/metrics/<name>.py``; a channel
whose ``init`` is ``{"maker": <name>, ...}`` is made by
``cellbench/inits/<name>.py``. A later change adds a cell by adding such
files and entries, and edits none.

A reader's ``read(ctx)`` gets the trace, the configuration, the traffic,
the window's ``work`` and its ``counters``. A grid cell's counters are
``calls`` and, for each number (an ``int`` or ``float``, not a
``bool``) in the program's ``Report.backend_report``,
``report.<key>``: its sum over the window's calls. A mean the program
reports per call, such as ``mean_active_fraction``, is its sum over
``calls``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

TRAFFIC_DIR = os.path.join("cellbench", "traffic")
METRICS_DIR = os.path.join("cellbench", "metrics")
INITS_DIR = os.path.join("cellbench", "inits")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    #: the end-to-end and per-layer metric entries this cell reports
    end_to_end: list
    per_layer: list
    root: str


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    """The cell named ``workload`` of ``<root>/BENCHMARK.json``, with its
    configuration and traffic read from their files."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, TRAFFIC_DIR, w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
        root=root)


def metric_module(root: str, name: str):
    """The module ``cellbench/metrics/<name>.py``, or, where there is no
    such file, that of ``name`` less its last dotted part: one reader
    serves ``device_idle_pct.grid`` and ``device_idle_pct.sweep``. Names
    may hold dots, so the file is loaded by path, not imported."""
    stem = name
    path = os.path.join(root, METRICS_DIR, stem + ".py")
    while not os.path.exists(path) and "." in stem:
        stem = stem.rsplit(".", 1)[0]
        path = os.path.join(root, METRICS_DIR, stem + ".py")
    return _load(path, "cellbench_metric_" + name)


def init_maker(root: str, name: str):
    """The ``make`` function of ``cellbench/inits/<name>.py``."""
    return _load(os.path.join(root, INITS_DIR, name + ".py"),
                 "cellbench_init_" + name).make


def _load(path: str, module: str):
    spec = importlib.util.spec_from_file_location(
        module.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: str, name: str):
    """The ``read`` function of ``cellbench/metrics/<name>.py``."""
    return metric_module(root, name).read
