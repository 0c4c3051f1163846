"""Per-topology pad-watermark policies (MultiSearch) and the CI
BENCH_sweep.json regression gate (benchmarks/compare_sweep.py)."""
import copy
import time

import numpy as np
import pytest

from benchmarks.compare_sweep import compare, stale_policy_warnings
from repro.core import jax_cost, search, trace
from repro.core.arch import ARCH_SPARSEMAP
from repro.core.search import (PAD_DECAYS, FleetConfig, MultiSearch,
                               PadPolicy, SearchTask, pad_policy_for,
                               set_pad_policy)
from repro.core.workload import spmm

WL_A = spmm("pad_a", 32, 64, 48, 0.2, 0.5)
WL_B = spmm("pad_b", 48, 32, 64, 0.4, 0.3)


def _fleet(**kw):
    tasks = [SearchTask(WL_A, "cloud", budget=300, seed=0,
                        method="random_mapper"),
             SearchTask(WL_B, "cloud", budget=300, seed=0,
                        method="sparsemap")]
    return MultiSearch(tasks, stack_batches=True, **kw)


def test_pad_watermark_history_recorded_per_topology():
    ms = _fleet()
    ms.run()
    fp = ARCH_SPARSEMAP.topology.fingerprint
    assert list(ms.stats["pad_policies"]) == [fp]
    # the paper topology carries the measured policy derived from the
    # committed baseline trajectory (configs.archs), not the default:
    # earlier decay (2 quiet rounds), ratio tightened to the observed
    # post-spike plateau (256/2048)
    assert ms.stats["pad_policies"][fp] == \
        {"decay_rounds": 2, "decay_ratio": 0.125, "source": "measured"}
    wms = ms.stats["pad_watermarks"]
    assert len(wms) == 1
    (key, hist), = wms.items()
    assert key.endswith(fp)
    assert len(hist) == ms.stats["rounds"]
    assert all(h >= 64 for h in hist)       # the pad floor


def test_pad_policy_override_and_registry():
    aggressive = PadPolicy(decay_rounds=1, decay_ratio=1.0)
    fp = ARCH_SPARSEMAP.topology.fingerprint
    ms = _fleet(pad_policies={fp: aggressive})
    res_o = ms.run()
    assert ms.stats["pad_policies"][fp] == \
        {"decay_rounds": 1, "decay_ratio": 1.0, "source": "default"}
    (_, hist_o), = ms.stats["pad_watermarks"].items()
    ms_d = _fleet()
    res_d = ms_d.run()
    (_, hist_d), = ms_d.stats["pad_watermarks"].items()
    # an always-decay policy tracks each round's own shape, so its
    # watermark can only be at or below the sticky default's
    assert len(hist_o) == len(hist_d)
    assert all(o <= d for o, d in zip(hist_o, hist_d))
    # padding rows are inert: results are identical under either policy
    for name in res_d:
        assert res_d[name].best_edp == res_o[name].best_edp
        assert np.array_equal(res_d[name].history, res_o[name].history)
    # the global registry is consulted when no override is passed
    try:
        set_pad_policy("deadbeef", aggressive)
        assert pad_policy_for("deadbeef") == aggressive
        assert pad_policy_for("not_registered") == PadPolicy()
    finally:
        search._PAD_POLICIES.pop("deadbeef", None)


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
                         FleetConfig(stack_batches=True))
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
    fp = ARCH_SPARSEMAP.topology.fingerprint
    assert ms.stats["pad_policies"][fp] == \
        {"decay_rounds": 2, "decay_ratio": 0.125, "source": "measured"}
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
        assert a["pad_watermarks"] and a["pad_policies"]
    k4 = {a["arch"]: a for a in base["archs"]}["cloud_device_k4"]
    assert k4["device_rounds"] == 4
    assert k4["host_syncs_per_round"] <= 1 / 4
    # no stale-policy warnings against the baseline itself: registered
    # policies must match what its own trajectories derive
    assert stale_policy_warnings(base) == []


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


def test_stale_policy_warning_fires_on_mismatched_trajectory():
    rec = dict(archs=[dict(
        arch="cloud",
        # one-off spike, never re-grows -> derivation says decay_rounds=2
        pad_watermarks={"d3_p16_feedf00d": [2048, 2048, 2048, 256, 256]},
        pad_policies={"feedf00d": {"decay_rounds": 3,
                                   "decay_ratio": 0.5}})])
    warns = stale_policy_warnings(rec)
    assert len(warns) == 1 and "decay_rounds=2" in warns[0]
    # re-growing trajectory matches the conservative registered policy
    rec["archs"][0]["pad_watermarks"]["d3_p16_feedf00d"] = \
        [2048, 256, 2048, 256, 2048]
    assert stale_policy_warnings(rec) == []


def test_stale_policy_warning_promotes_seed_policies():
    """A policy still carrying source="seed" after a run that measured
    the topology's real trajectory asks for promotion to the baseline
    watermark table — even when decay_rounds already agrees."""
    rec = dict(archs=[dict(
        arch="sigma_like",
        pad_watermarks={"d3_p16_8b2430a8": [2048, 2048, 256, 256]},
        pad_policies={"8b2430a8": {"decay_rounds": 2,
                                   "decay_ratio": 0.125,
                                   "source": "seed"}})])
    warns = stale_policy_warnings(rec)
    assert len(warns) == 1
    assert "seed pad policy" in warns[0]
    assert "_SEED_PAD_WATERMARKS" in warns[0]
    # once promoted (source measured), the same record is quiet
    rec["archs"][0]["pad_policies"]["8b2430a8"]["source"] = "measured"
    assert stale_policy_warnings(rec) == []


def test_compare_sweep_fails_when_arch_disappears():
    cur = copy.deepcopy(BASE)
    cur["archs"] = cur["archs"][:1]
    failures, _ = compare(BASE, cur)
    assert failures == ["maple_edge: arch disappeared from the sweep"]
