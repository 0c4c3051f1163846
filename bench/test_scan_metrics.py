"""CPU checks of the segment-scan cell and its metric readers:

* each of the four readers on recorder events planted in a window, and
  None where the ring has dropped the window's start or the program
  counts nothing the reader reads;
* ``t3_conv_eyeriss_k8.es`` loads through ``cell.load_cell`` with the
  workloads, accelerator and limits of ``t3_conv_eyeriss`` at
  ``device_rounds`` 8;
* the designs of a small device_rounds-8 fleet on conv1-conv3, priced by
  the reference through ``check.Pricer``, within ``edp_gap_log10``.
"""
from __future__ import annotations

import json
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".bench_cache", "jax-test"))

import cell  # noqa: E402
import check  # noqa: E402

CELL = "t3_conv_eyeriss_k8.es"
SIG = (3, 32, "cafe0123", "u")


def _planted():
    """A window holding one stacked and two scan dispatches' counters and
    two scan builds, and the metric context around it."""
    from repro.core import trace
    t_open = time.perf_counter()
    trace.count("fleet.rows", 1000, sig=SIG, kind="stacked")
    trace.count("fleet.rows_padded", 48, sig=SIG, kind="stacked")
    for tasks, slots in ((13, 16), (7, 8)):
        trace.count("fleet.rows", tasks * 720, sig=SIG, kind="scan")
        trace.count("fleet.rows_padded", (slots - tasks) * 720, sig=SIG,
                    kind="scan")
        trace.count("fleet.scan_tasks", tasks, sig=SIG, slots=slots)
    trace.count("fleet.scan_builds", sig=SIG, slots=16, source="inline")
    trace.count("fleet.scan_builds", sig=SIG, slots=8, source="ahead")
    t_close = time.perf_counter()
    return dict(t_open=t_open, t_close=t_close, window_s=t_close - t_open,
                trace=dict(module_s={"jit_one_task": 0.0288,
                                     "jit_eval_one": 0.5}),
                trace_lo=t_open, trace_hi=t_close)


@pytest.mark.parametrize("metric,want", [
    ("scan_eval_share", 20 * 720 / (1000 + 20 * 720)),
    ("scan_task_pad_share", (3 + 1) / (16 + 8)),
    ("scan_builds", 2),
    ("scan_us_per_eval", 0.0288 / (20 * 720) * 1e6),
])
def test_reader_on_planted_events(metric, want):
    assert cell.reader(metric)(_planted()) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["scan_eval_share",
                                    "scan_task_pad_share", "scan_builds",
                                    "scan_us_per_eval"])
def test_reader_is_none_once_the_ring_dropped_the_window(metric,
                                                         monkeypatch):
    from repro.core import trace
    ctx = _planted()
    monkeypatch.setattr(trace, "_DROPPED_T1", ctx["t_open"])
    assert cell.reader(metric)(ctx) is None


def test_readers_are_none_for_a_program_without_the_counters():
    """Row counters without a kind, and no scan counters: what a program
    without scan buckets records."""
    from repro.core import trace
    t_open = time.perf_counter()
    trace.count("fleet.rows", 500, sig=SIG)
    ctx = dict(t_open=t_open, t_close=time.perf_counter(),
               trace=dict(module_s={"jit_one_task": 0.01}),
               trace_lo=t_open, trace_hi=time.perf_counter())
    for metric in ("scan_eval_share", "scan_task_pad_share",
                   "scan_us_per_eval"):
        assert cell.reader(metric)(ctx) is None, metric
    assert cell.reader("scan_us_per_eval")(dict(ctx, trace=None)) is None


def test_the_k8_cell_is_the_conv_cell_at_device_rounds_8():
    c = cell.load_cell(CELL)
    base = json.load(open(os.path.join(BENCH, "configs",
                                       "t3_conv_eyeriss.json")))
    cfg = c["config"]
    assert cfg["fleet"] == dict(base["fleet"], device_rounds=8)
    for key in base:
        if key not in ("name", "source", "deployment", "fleet"):
            assert cfg[key] == base[key], key
    assert c["chips"] == 1 and c["traffic"]["methods"] == ["sparsemap"]
    import run
    wls = run.build_workloads(cfg)
    assert list(wls) == [f"conv{i}" for i in range(1, 14)]
    assert {m["name"] for m in c["per_layer"]} >= {
        "scan_eval_share", "scan_task_pad_share", "scan_builds",
        "scan_us_per_eval"}


def test_k8_fleet_designs_match_the_reference():
    """A device_rounds-8 fleet of conv1-conv3 at a small budget: each
    query's best design, priced by the float64 reference, within the
    configuration's edp_gap_log10."""
    import run
    from repro.core.search import FleetConfig, MultiSearch, SearchTask
    cfg = cell.load_cell(CELL)["config"]
    wls = run.build_workloads(cfg)
    arch = cfg["accelerator"]["name"]
    names = ["conv1", "conv2", "conv3"]
    ms = MultiSearch([SearchTask(wls[n], arch, budget=2400, seed=7)
                      for n in names], FleetConfig(**cfg["fleet"]))
    res = ms.run()
    # the main phases ran as scans: one host sync per 8 generations
    assert ms.stats["host_syncs_per_round"] == pytest.approx(1 / 8)
    designs = []
    for n, name in zip(names, ms.final_names):
        r = res[name]
        designs.append((n, tuple(int(x) for x in r.best_genome),
                        float(r.best_edp)))
    gap, invalid, n = check.compare(designs, check.Pricer(cfg))
    assert (invalid, n) == (0, 3)
    assert gap <= cfg["limits"]["edp_gap_log10"]
