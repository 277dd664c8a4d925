"""Finds a cell's pieces by name: ``BENCHMARK.json`` at the checkout
root names the cell's configuration, traffic mix and metrics; each
lives in a file of its own under ``bench/``:

- ``bench/configs/<config>.json``: the model (Hugging Face keys), the
  engine settings and the limit of the correctness check;
- ``bench/traffic/<traffic>.json``: the mix, read by ``bench.traffic``;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric, a
  function ``read(run) -> float | None``.

A new cell, configuration, mix or metric is new files and new entries
in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Metric:
    name: str
    unit: str


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                      # bench/configs/<config>.json
    traffic: dict                     # bench/traffic/<traffic>.json
    end_to_end: List[Metric]          # those this cell reports
    per_layer: List[Metric]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def _metrics(entries: List[dict], cell: str) -> List[Metric]:
    return [Metric(e["name"], e["unit"]) for e in entries
            if _applies(e, cell)]


def load_cell(name: str, root: str = ROOT,
              bench_dir: Optional[str] = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files
    read from ``bench_dir`` (default: ``<root>/bench``)."""
    bench_dir = bench_dir or os.path.join(root, "bench")
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=_metrics(spec["end_to_end"], name),
                per_layer=_metrics(spec["per_layer"], name))


def reader(metric: str, bench_dir: str = BENCH_DIR
           ) -> Callable[[object], Optional[float]]:
    """``read`` of ``bench/metrics/<metric>.py``, loaded by path (metric
    names hold dots, so they are no module names)."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[Metric], run, bench_dir: str = BENCH_DIR
                 ) -> Dict[str, dict]:
    """Every metric whose reader found something to read."""
    out = {}
    for m in metrics:
        v = reader(m.name, bench_dir)(run)
        if v is not None:
            out[m.name] = {"value": v, "unit": m.unit}
    return out
