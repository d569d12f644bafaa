"""Finding a cell's files by the names in ``BENCHMARK.json``. Nothing about
any one cell, mix, configuration or metric is written in code: a later PR
adds entries and files, and edits none that are there."""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _read(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]          # benchmark/configs/<config>.json
    traffic: Dict[str, Any]         # benchmark/traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    def limits(self) -> Dict[str, float]:
        """What ``correct`` holds each compared number to: the
        configuration's limits, and over them those it states for this mix
        (``limits_by_traffic``: a limit lies between the sound runs' and the
        control's readings, and a mix on which both read lower than on the
        others needs one of its own to tell them apart)."""
        return {**self.config.get("limits", {}),
                **self.config.get("limits_by_traffic", {}).get(
                    self.traffic_name, {})}


def kept_cells(bench_dir: str) -> Dict[str, Any]:
    """benchmark/kept_cells.json: cells that left ``workloads`` and stay on
    file (their configuration, mix, band and limits) until a ``benchmark``
    PR brings them back: {"workloads": [...]}, each entry as
    ``BENCHMARK.json`` had it, with ``per_layer`` (the metrics that named
    it), ``left`` and ``returns_when`` (and ``readings``, where the cell was
    measured and not admitted). {} where there is none."""
    path = os.path.join(bench_dir, "kept_cells.json")
    return _read(path) if os.path.exists(path) else {}


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json``; failing that, the one of
    that name kept on file: the driver runs the first kind only, the tests
    and the builder of the PR that brings one back run the second."""
    bench_dir = os.path.join(root, "benchmark")
    spec = _read(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in spec["workloads"] if w["name"] == workload), None)
    listed: List[str] = []
    if cell is None:
        cell = next((w for w in kept_cells(bench_dir).get("workloads", [])
                     if w["name"] == workload), None)
        if cell is None:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        listed = cell.get("per_layer", [])
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])

    def applies(metric: Dict[str, Any]) -> bool:
        return "workloads" not in metric or workload in metric["workloads"] \
            or metric["name"] in listed

    return Cell(
        name=workload, chips=int(cell["chips"]),
        config_name=cell["config"], traffic_name=cell["traffic"],
        config=_read(os.path.join(root, conf["file"])),
        traffic=_read(os.path.join(bench_dir, "traffic",
                                   cell["traffic"] + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)])


def layer_metric_spec(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """benchmark/layer_metrics/<name>.json: {"reader": ..., "args": {...}}"""
    return _read(os.path.join(bench_dir, "layer_metrics", name + ".json"))


def module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """<bench_dir>/<kind>/<name>.py: kind is sources, readers or reference.
    A benchmark directory other than this one (a test's copy with files
    added) lends its modules to the same packages."""
    pkg = importlib.import_module(f"benchmark.{kind}")
    extra = os.path.join(bench_dir, kind)
    if extra not in pkg.__path__:
        pkg.__path__.append(extra)
    return importlib.import_module(f"benchmark.{kind}.{name}")
