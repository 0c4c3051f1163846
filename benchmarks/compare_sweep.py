"""Compare a freshly generated ``BENCH_sweep.json`` against the committed
baseline run (``benchmarks/BENCH_sweep.baseline.json``, regenerated with
``benchmarks.run --quick --only sweep_json`` whenever a PR intentionally
moves the counts) — the CI perf-regression gate.

    PYTHONPATH=src python -m benchmarks.compare_sweep \
        --baseline benchmarks/BENCH_sweep.baseline.json \
        --current BENCH_sweep.new.json

Hard failures (exit 1): a per-arch XLA compile-count increase, a
dispatches-per-round increase, a host-syncs-per-round increase, or a
compile-ahead-miss increase (the AOT predictor losing coverage of a
round-1 signature), compared arch-by-arch over the archs present in
BOTH files (a newly added arch has no baseline and is reported, not
failed).  Timing — seconds, seconds_per_round, host_blocked_s, and the
pipelined-vs-unpipelined host_blocked_s comparison — is warn-only: CI
machines are too noisy to gate on wall-clock.  When the two runs used
different budgets the counts are not comparable either, so everything
downgrades to warnings.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Tuple

#: warn when an arch's wall-clock grows beyond this factor
TIME_WARN_RATIO = 1.5


def compare(baseline: dict, current: dict) -> Tuple[List[str], List[str]]:
    """(failures, warnings) between two bench_sweep_json records."""
    failures: List[str] = []
    warnings: List[str] = []
    comparable = baseline.get("budget") == current.get("budget")
    if not comparable:
        warnings.append(
            f"budgets differ (baseline {baseline.get('budget')} vs "
            f"current {current.get('budget')}): compile/dispatch counts "
            f"are not comparable, downgrading all checks to warnings")
    base_archs: Dict[str, dict] = {a["arch"]: a
                                   for a in baseline.get("archs", [])}
    cur_archs: Dict[str, dict] = {a["arch"]: a
                                  for a in current.get("archs", [])}
    for name in cur_archs:
        if name not in base_archs:
            warnings.append(f"{name}: new arch, no baseline to compare")
    for name, base in base_archs.items():
        sink = failures if comparable else warnings
        cur = cur_archs.get(name)
        if cur is None:
            sink.append(f"{name}: arch disappeared from the sweep")
            continue
        if cur["compiles"] > base["compiles"]:
            sink.append(
                f"{name}: compiles regressed "
                f"{base['compiles']} -> {cur['compiles']}")
        if cur["dispatches_per_round"] > base["dispatches_per_round"]:
            sink.append(
                f"{name}: dispatches/round regressed "
                f"{base['dispatches_per_round']} -> "
                f"{cur['dispatches_per_round']}")
        # host-sync regression: a device-resident fleet losing its k-round
        # segments (or a per-round fleet growing extra host round-trips)
        # shows up here even when dispatch counts stay flat
        base_hspr = base.get("host_syncs_per_round")
        cur_hspr = cur.get("host_syncs_per_round")
        if base_hspr is not None and cur_hspr is not None and \
                cur_hspr > base_hspr:
            sink.append(
                f"{name}: host syncs/round regressed "
                f"{base_hspr} -> {cur_hspr}")
        # compile-ahead coverage: the predictor failing to claim a
        # round-1 signature it used to cover is a hard regression (a
        # miss means a fresh jit trace landed on the fleet's critical
        # path); hit-count drift and all timing fields stay warn-only
        base_ca = base.get("compile_ahead_misses")
        cur_ca = cur.get("compile_ahead_misses")
        if base_ca is not None and cur_ca is not None and \
                cur_ca > base_ca:
            sink.append(
                f"{name}: compile-ahead misses regressed "
                f"{base_ca} -> {cur_ca}")
        if base.get("seconds") and cur.get("seconds", 0.0) > \
                TIME_WARN_RATIO * base["seconds"]:
            warnings.append(
                f"{name}: {cur['seconds']:.2f}s vs baseline "
                f"{base['seconds']:.2f}s (> {TIME_WARN_RATIO}x, "
                f"warn-only)")
        base_hb = base.get("host_blocked_s")
        cur_hb = cur.get("host_blocked_s", 0.0)
        if base_hb and cur_hb > TIME_WARN_RATIO * base_hb:
            warnings.append(
                f"{name}: host_blocked_s {cur_hb:.4f} vs baseline "
                f"{base_hb:.4f} (> {TIME_WARN_RATIO}x, warn-only)")
    # pipelining acceptance (warn-only, it is a timing measure): the
    # pipelined device fleet should spend strictly less host-blocked
    # wall-clock than its unpipelined twin in the SAME run
    # contract analysis: a violation recorded in the sweep is a hard
    # failure regardless of budget — `python -m repro.analysis` should
    # have caught it pre-merge, the sweep record carries it as artifact
    # provenance
    cur_an = current.get("analysis")
    if cur_an is not None and cur_an.get("violations", 0) > 0:
        failures.append(
            f"analysis: {cur_an['violations']} contract violation(s) "
            f"recorded in the sweep (run `python -m repro.analysis`)")
    # canonical kernel-family jaxpr hashes: drift is warn-only
    base_h = baseline.get("jaxpr_hashes") or {}
    cur_h = current.get("jaxpr_hashes") or {}
    for fam in sorted(set(base_h) & set(cur_h)):
        if base_h[fam] != cur_h[fam]:
            warnings.append(
                f"jaxpr hash drift for kernel family {fam}: "
                f"{base_h[fam]} -> {cur_h[fam]} (warn-only; expected "
                f"only when a PR intentionally changes the kernel)")
    for fam in sorted(set(base_h) - set(cur_h)):
        warnings.append(f"kernel family {fam} disappeared from the "
                        f"jaxpr_hashes record")
    pipe = cur_archs.get("cloud_device_k4")
    nopipe = cur_archs.get("cloud_device_k4_unpipelined")
    if pipe is not None and nopipe is not None and \
            pipe.get("host_blocked_s") is not None and \
            pipe["host_blocked_s"] >= nopipe.get("host_blocked_s", 0.0):
        warnings.append(
            f"cloud_device_k4: pipelined host_blocked_s "
            f"{pipe['host_blocked_s']} not below unpipelined "
            f"{nopipe.get('host_blocked_s')} (warn-only)")
    return failures, warnings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", required=True,
                    help="committed BENCH_sweep.json")
    ap.add_argument("--current", required=True,
                    help="freshly generated sweep record")
    args = ap.parse_args(argv)
    with open(args.baseline) as f:
        baseline = json.load(f)
    with open(args.current) as f:
        current = json.load(f)
    failures, warnings = compare(baseline, current)
    for w in warnings:
        print(f"WARN: {w}")
    for x in failures:
        print(f"FAIL: {x}")
    if failures:
        return 1
    print(f"OK: {len(baseline.get('archs', []))} baseline archs compared, "
          f"no compile/dispatch regressions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
