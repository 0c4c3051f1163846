"""Find everything one cell needs by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix.  The configuration's
file is the one BENCHMARK.json gives; the traffic mix is
``bench/traffic/<traffic>.json``; each metric is read by
``bench/metrics/<metric>.py``, whose ``read(ctx)`` returns a number, or
None when the run gave it nothing to read.  A later cell or metric is
added by adding such files and entries, with no edit here.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class CellError(Exception):
    """The cell, or a file it names, is missing or malformed."""


def _load_json(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise CellError(f"cannot read {os.path.relpath(path, ROOT)}: {e}")


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Dict:
    """The cell's BENCHMARK.json entry joined with its configuration,
    its traffic mix and the metrics it reports."""
    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise CellError(f"cell {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(ROOT, "bench", "traffic",
                                      f"{w['traffic']}.json"))
    return dict(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def reader(metric: str, root: str = ROOT) -> Callable[[Dict], Optional[float]]:
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{metric}.py")
    if not os.path.isfile(path):
        raise CellError(f"no reader for metric {metric!r} at "
                        f"{os.path.relpath(path, root)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[Dict], ctx: Dict,
                 root: str = ROOT) -> Dict[str, Dict]:
    """Every metric whose reader finds something to read, with its
    unit; a reader that returns None leaves its metric out."""
    out = {}
    for m in metrics:
        v = reader(m["name"], root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
