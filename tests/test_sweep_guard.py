"""The mega-batch pad watermark's grow/decay rule (MultiSearch) and the
CI BENCH_sweep.json regression gate (benchmarks/compare_sweep.py)."""
import copy
import time

import numpy as np
import pytest

from benchmarks.compare_sweep import compare
from repro.core import jax_cost, trace
from repro.core.arch import ARCH_SPARSEMAP
from repro.core.arch_dsl import compile_arch
from repro.core.search import (PAD_DECAY_ROUNDS, PAD_DECAYS, FleetConfig,
                               MultiSearch, SearchTask)
from repro.core.workload import spmm

WL_A = spmm("pad_a", 32, 64, 48, 0.2, 0.5)
WL_B = spmm("pad_b", 48, 32, 64, 0.4, 0.3)


def _fleet(platform="cloud", budget=300):
    tasks = [SearchTask(WL_A, platform, budget=budget, seed=0,
                        method="random_mapper"),
             SearchTask(WL_B, platform, budget=budget, seed=0,
                        method="sparsemap")]
    return MultiSearch(tasks)


def _first_decay(hist):
    """(rounds the watermark held its round-1 value, its value after)."""
    held = next(i for i, h in enumerate(hist) if h != hist[0])
    return held, hist[held]


def test_pad_watermark_history_recorded_per_topology():
    ms = _fleet()
    ms.run()
    fp = ARCH_SPARSEMAP.topology.fingerprint
    wms = ms.stats["pad_watermarks"]
    assert len(wms) == 1
    (key, hist), = wms.items()
    assert key.endswith(fp)
    assert len(hist) == ms.stats["rounds"]
    assert all(h >= 64 for h in hist)       # the pad floor


def test_unregistered_topology_decays_like_the_zoo():
    """One decay rule for every topology: on an arch declared here,
    whose topology no configuration registers, the round-1 spike (the
    random mapper's first chunk) falls after PAD_DECAY_ROUNDS = 2 quiet
    rounds, as it does on the paper's cloud arch."""
    from repro.configs.archs import ACCEL_ARCHS, ZOO_ARCHS
    arch = compile_arch({
        "name": "pad_rule_probe",
        "levels": [
            {"name": "dram"},
            {"name": "glb", "capacity": "48KB",
             "energy": [["dram", [90.0]]], "sg_site": "L2"},
            {"name": "reg", "energy": [["glb", [2.5]]], "fanout": [4, 4],
             "noc": {"multicast": "row", "reduction": ["cluster", 2]}},
        ],
    })
    known = {a.topology.fingerprint
             for a in (ARCH_SPARSEMAP, *ZOO_ARCHS.values(),
                       *ACCEL_ARCHS.values())}
    assert arch.topology.fingerprint not in known
    hists = []
    for platform in (arch, "cloud"):
        ms = _fleet(platform, budget=600)
        assert all(r.evals >= 600 for r in ms.run().values())
        (hist,) = ms.stats["pad_watermarks"].values()
        hists.append(hist)
        # the spike's round and the first quiet round hold it; the
        # second quiet round decays it
        assert _first_decay(hist)[0] == PAD_DECAY_ROUNDS == 2
        assert _first_decay(hist)[1] < hist[0]
    assert _first_decay(hists[0]) == _first_decay(hists[1])


def _admitting_fleet(pin_floor: int = 0):
    """One search whose first round pads to 512, two more admitted at
    step 5 (a 2,048-row spike, then 512 until the first retires, then
    256), a fourth at step 40 (512 again until it retires); at the end
    one search is left on 128.  Returns the fleet, its results and one
    ``(rows, floor, dispatched, compiles)`` record per mega-batch;
    ``pin_floor`` raises every dispatch's ``pad_floor`` to it."""
    log = []
    dispatch = jax_cost.eval_stacked

    def logged(models, batches, pad_floor=0, mesh=None, defer=False):
        floor = max(pad_floor, pin_floor)
        rows = sum(len(b) for b in batches)
        c0 = jax_cost.compilation_count()
        out = dispatch(models, batches, pad_floor=floor, mesh=mesh,
                       defer=defer)
        log.append((rows, pad_floor, jax_cost.stacked_rows(rows, floor),
                    jax_cost.compilation_count() - c0))
        return out

    admit = {5: [SearchTask(WL_A, "cloud", budget=10000, seed=0),
                 SearchTask(WL_B, "cloud", budget=6000, seed=1)],
             40: [SearchTask(WL_B, "cloud", budget=3000, seed=3)]}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax_cost, "eval_stacked", logged)
        ms = MultiSearch([SearchTask(WL_B, "cloud", budget=3000, seed=2)],
                         FleetConfig())
        steps = 0
        while ms.step():
            steps += 1
            for task in admit.get(steps, []):
                ms.admit(task)
        res = ms.finish()
    return ms, res, log


@pytest.fixture(scope="module")
def admitted_spikes():
    t0 = time.perf_counter()
    ms, res, log = _admitting_fleet()
    decays = trace.events(t0, time.perf_counter(), {PAD_DECAYS})
    _, pinned, pinned_log = _admitting_fleet(pin_floor=2048)
    return dict(ms=ms, res=res, log=log, decays=decays, pinned=pinned,
                pinned_log=pinned_log)


@pytest.mark.parametrize("case", ["warm_return", "cold_after_warm",
                                  "cold_waits",
                                  "results_match_pinned_floor"])
def test_watermark_falls_back_to_shapes_the_fleet_has_run(admitted_spikes,
                                                          case):
    ms, log = admitted_spikes["ms"], admitted_spikes["log"]
    (wm,) = ms.stats["pad_watermarks"].values()
    targets = [jax_cost._pad_batch(rows) for rows, _, _, _ in log]
    spike = targets.index(2048)
    first_256 = targets.index(256)
    again = targets.index(512, first_256)       # the step-40 admission
    back = targets.index(256, again)            # ... has retired
    if case == "warm_return":
        # 512 (ran in round 1) and 256 cannot pass the ratio test
        # against 2,048 and 512 (0.125 of each is 256 and 64), but both
        # have run, so two quiet rounds after each spike the floor
        # returns to them, with no new trace
        assert wm[spike + 1] == 2048 and wm[spike + 2] == 512
        assert wm[back] == 512 and wm[back + 1] == 256
        assert all(d == 256 for _, _, d, _ in log[back + 2:])
        assert all(c == 0 for _, _, _, c in log[back + 1:])
        assert [e.attrs["warm"] for e in admitted_spikes["decays"]] == \
            [True, False, True]
        assert ms.stats["pad_decays"] == {"warm": 2, "cold": 1}
    elif case == "cold_after_warm":
        # the warm fall to 512 leaves the ratio test measured against
        # the 2,048 peak, so 256, never run, still follows by it
        assert wm[first_256] == 512 and wm[first_256 + 1] == 256
        assert 256 not in {d for _, _, d, _ in log[:first_256 + 2]}
    elif case == "cold_waits":
        # 128 rows' shape never ran and fails the ratio test against
        # 512, so the floor holds at 256 and nothing is traced
        low = targets.index(128, back)
        assert low > back + 2 and len(targets) - low >= 3
        assert wm[low:] == [256] * (len(wm) - low)
        assert 128 not in {d for _, _, d, _ in log}
        assert all(c == 0 for _, _, _, c in log[low:])
    else:
        # padding rows are inert: a floor pinned at the peak changes
        # the dispatched shapes, never a searched statistic
        pinned = admitted_spikes["pinned"]
        assert {d for _, _, d, _ in admitted_spikes["pinned_log"]} == \
            {2048}
        assert sorted(pinned) == sorted(admitted_spikes["res"])
        for name, r in admitted_spikes["res"].items():
            p = pinned[name]
            assert (r.best_edp, r.evals, r.valid_evals) == \
                (p.best_edp, p.evals, p.valid_evals)
            assert np.array_equal(r.history, p.history)


# ------------------------------------------------- compare_sweep gate


BASE = dict(
    budget=300,
    archs=[
        dict(arch="cloud", seconds=10.0, compiles=2,
             dispatches_per_round=1.0),
        dict(arch="maple_edge", seconds=5.0, compiles=2,
             dispatches_per_round=1.0),
    ])


def test_compare_sweep_passes_on_identical_runs():
    failures, warnings = compare(BASE, copy.deepcopy(BASE))
    assert failures == [] and warnings == []


def test_compare_sweep_fails_on_compile_and_dispatch_regressions():
    cur = copy.deepcopy(BASE)
    cur["archs"][0]["compiles"] = 3
    cur["archs"][1]["dispatches_per_round"] = 2.0
    failures, _ = compare(BASE, cur)
    assert len(failures) == 2
    assert "compiles regressed 2 -> 3" in failures[0]
    assert "dispatches/round regressed" in failures[1]


def test_compare_sweep_new_arch_and_timing_are_warn_only():
    cur = copy.deepcopy(BASE)
    cur["archs"].append(dict(arch="quant_edge", seconds=1.0, compiles=9,
                             dispatches_per_round=3.0))
    cur["archs"][0]["seconds"] = 100.0
    failures, warnings = compare(BASE, cur)
    assert failures == []
    assert any("new arch" in w for w in warnings)
    assert any("warn-only" in w for w in warnings)


def test_compare_sweep_budget_mismatch_downgrades_to_warnings():
    cur = copy.deepcopy(BASE)
    cur["budget"] = 1000
    cur["archs"][0]["compiles"] = 99
    del cur["archs"][1]                 # disappearance downgrades too
    failures, warnings = compare(BASE, cur)
    assert failures == []
    assert any("budgets differ" in w for w in warnings)
    assert any("compiles regressed" in w for w in warnings)
    assert any("disappeared" in w for w in warnings)


def test_committed_baseline_is_well_formed():
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "BENCH_sweep.baseline.json")
    base = json.load(open(path))
    failures, warnings = compare(base, base)
    assert failures == [] and warnings == []
    assert {a["arch"] for a in base["archs"]} >= \
        {"cloud", "maple_edge", "cluster_cloud", "systolic_mesh",
         "quant_edge"}
    for a in base["archs"]:
        # per-round fleets hold 1 dispatch/round; the device-resident
        # fleet (cloud_device_k4) folds k generations per dispatch
        assert a["dispatches_per_round"] <= 1.0
        assert a["host_syncs_per_round"] <= 1.0
        assert a["pad_watermarks"]
    k4 = {a["arch"]: a for a in base["archs"]}["cloud_device_k4"]
    assert k4["device_rounds"] == 4
    assert k4["host_syncs_per_round"] <= 1 / 4


def test_compare_sweep_fails_on_host_sync_regression():
    base = copy.deepcopy(BASE)
    base["archs"][0]["host_syncs_per_round"] = 0.25
    cur = copy.deepcopy(base)
    cur["archs"][0]["host_syncs_per_round"] = 1.0
    failures, _ = compare(base, cur)
    assert failures == ["cloud: host syncs/round regressed 0.25 -> 1.0"]
    # absent on either side (old baseline) -> not comparable, no failure
    failures, _ = compare(BASE, cur)
    assert failures == []


def test_compare_sweep_fails_when_arch_disappears():
    cur = copy.deepcopy(BASE)
    cur["archs"] = cur["archs"][:1]
    failures, _ = compare(BASE, cur)
    assert failures == ["maple_edge: arch disappeared from the sweep"]
