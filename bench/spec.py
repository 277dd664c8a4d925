"""Finds a cell's pieces by name: ``BENCHMARK.json`` at the checkout
root names the cell's configuration, traffic mix and metrics; each
lives in a file of its own under ``bench/``:

- ``bench/configs/<config>.json``: the model (Hugging Face keys), the
  name of its model module (``"module"``), the engine settings and the
  limit of the correctness check;
- ``bench/models/<module>.py``: what the harness needs of one model
  family, the functions named in ``MODEL_API``: the fields the
  program's registry entry must hold, the seeded weights, the plain
  reference and the counts of work (``bench/models/dense.py`` says how);
- ``bench/traffic/<traffic>.json``: the mix, read by ``bench.traffic``;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric, a
  function ``read(run) -> float | None``.

A new cell, mix or metric is new files and new entries in
``BENCHMARK.json``; a new configuration is its JSON file and, where no
model module fits it, its own module, and new entries.  Nothing here
changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# what every model module defines (``bench/models/dense.py`` documents
# each); ``model`` refuses a module that lacks one
MODEL_API = ("arch_fields", "weights", "forward", "decode_flops",
             "attn_flops", "paged_attn_bytes", "prefill_flops_per_token",
             "weight_bytes_per_step")


@dataclass
class Metric:
    name: str
    unit: str


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                      # bench/configs/<config>.json
    model: ModuleType                 # bench/models/<module>.py
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
                model=model(config["module"], bench_dir), traffic=traffic,
                end_to_end=_metrics(spec["end_to_end"], name),
                per_layer=_metrics(spec["per_layer"], name))


def _load(path: str, name: str) -> ModuleType:
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod          # dataclasses look their module up
    mod_spec.loader.exec_module(mod)
    return mod


@functools.cache
def _model_at(path: str) -> ModuleType:
    # one module object per file, so its jitted functions compile once
    mod = _load(path, "bench_model_" + os.path.basename(path)[:-3])
    missing = [f for f in MODEL_API if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"model module {path} lacks {missing}")
    return mod


def model(name: str, bench_dir: str = BENCH_DIR) -> ModuleType:
    """``bench/models/<name>.py``, the model module a configuration
    file names, loaded by path.  This is the one way a module is
    loaded, by the harness, by tests and by a module that builds on
    another (``sibling``)."""
    return _model_at(os.path.join(os.path.abspath(bench_dir), "models",
                                  name + ".py"))


def sibling(name: str, module_file: str) -> ModuleType:
    """The model module ``name`` beside the module file ``module_file``
    (pass ``__file__``): how one model module builds on another of the
    same checkout."""
    return model(name, os.path.dirname(os.path.dirname(
        os.path.abspath(module_file))))


def reader(metric: str, bench_dir: str = BENCH_DIR
           ) -> Callable[[object], Optional[float]]:
    """``read`` of ``bench/metrics/<metric>.py``, loaded by path (metric
    names hold dots, so they are no module names)."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    return _load(path, "bench_metric_" + metric.replace(".", "_")).read


def read_metrics(metrics: List[Metric], run, bench_dir: str = BENCH_DIR
                 ) -> Dict[str, dict]:
    """Every metric whose reader found something to read."""
    out = {}
    for m in metrics:
        v = reader(m.name, bench_dir)(run)
        if v is not None:
            out[m.name] = {"value": v, "unit": m.unit}
    return out
