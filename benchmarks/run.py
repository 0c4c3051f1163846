"""Benchmark harness: one benchmark per paper table/figure + the roofline
table from the dry-run.  Prints ``name,seconds,derived`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run [--budget N] [--quick] [--full]
    PYTHONPATH=src python -m benchmarks.run --only fig18

``--only sweep_json`` (also run by default) additionally writes the
machine-readable ``BENCH_sweep.json`` perf-trajectory record — XLA
compilations, dispatches/round, per-topology pad-watermark
trajectories, and best-EDP per method x workload x arch — which CI
uploads as an artifact AND gates against the committed
``benchmarks/BENCH_sweep.baseline.json`` (compile-count or
dispatches-per-round regressions fail the build; see
``benchmarks.compare_sweep``).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

SWEEP_JSON = os.environ.get("REPRO_BENCH_SWEEP_JSON", "BENCH_sweep.json")


def bench_sweep_json(budget: int, out_path: str = SWEEP_JSON) -> dict:
    """One stacked ``run_method_sweep`` fleet per registered arch, plus
    one structured-density fleet (the 2:4 sparseGPT BlockNM family + a
    banded-attention workload + a uniform control as ONE mega-batched
    signature — density families/params are traced, so the compile count
    must stay flat across the family); the per-cell best-EDPs plus
    fleet-level compile/dispatch counts land in ``out_path`` as JSON."""
    from repro.configs.paper_workloads import (banded_attention_workloads,
                                               by_name)
    from repro.core import jax_cost, search

    methods = ["sparsemap", "random_mapper", "pso"]
    wls = [by_name(n) for n in ("mm1", "mm3")]
    archs = ["cloud", "maple_edge", "cluster_cloud", "systolic_mesh",
             "quant_edge", "eyeriss_like", "sigma_like", "dstc_like"]
    record = dict(budget=budget, methods=methods,
                  workloads=[w.name for w in wls], archs=[], cells=[])

    def run_fleet(entry_name, fleet_methods, fleet_wls, arch,
                  fleet_budget=None, **fleet_kw):
        search.clear_cache()
        stats: dict = {}
        t0 = time.time()
        config = search.FleetConfig(**fleet_kw)
        grid = search.run_method_sweep(fleet_methods, fleet_wls, arch,
                                       budget=fleet_budget or budget,
                                       seed=0, stats_out=stats,
                                       config=config)
        seconds = round(time.time() - t0, 2)
        arec = dict(
            arch=entry_name, seconds=seconds,
            budget=fleet_budget or budget,
            compiles=jax_cost.compilation_count(),
            rounds=stats["rounds"], dispatches=stats["dispatches"],
            dispatches_per_round=round(
                stats["dispatches"] / max(stats["rounds"], 1), 3),
            seconds_per_round=round(
                seconds / max(stats["rounds"], 1), 4),
            # host round-trips per search generation: 1.0 for per-round
            # fleets, ~1/k in the segment phase of device_rounds=k fleets
            host_syncs=stats["host_syncs"],
            host_syncs_per_round=round(stats["host_syncs_per_round"], 3),
            device_rounds=stats["device_rounds"],
            device_rounds_source=stats["device_rounds_source"],
            # pipelining record: wall-clock the host spent blocked in
            # device->numpy conversions, and the AOT compile-ahead
            # coverage of the fleet's round-1 dispatch signatures
            # (misses are gated by compare_sweep; timing is warn-only)
            host_blocked_s=round(stats["host_blocked_s"], 4),
            compile_ahead_hits=stats["compile_ahead_hits"],
            compile_ahead_misses=stats["compile_ahead_misses"],
            pipeline=stats["pipeline"],
            n_devices=stats["devices"],
            signatures=[list(s) for s in stats["signatures"]],
            # per-topology mega-batch watermark trajectory — the
            # cross-PR record of the retrace-vs-padded-compute trade-off
            pad_watermarks=stats.get("pad_watermarks", {}))
        record["archs"].append(arec)
        for m in fleet_methods:
            for w in fleet_wls:
                r = grid[m][w.name]
                record["cells"].append(dict(
                    arch=entry_name, method=m, workload=w.name,
                    best_edp=(float(r.best_edp)
                              if np.isfinite(r.best_edp) else None),
                    evals=int(r.evals), valid_evals=int(r.valid_evals)))

    for arch in archs:
        run_fleet(arch, methods, wls, arch)

    # structured-density mixed fleet on the paper arch: BlockNM(2,4)
    # family (mm8-mm10) + banded attention + uniform mm1 — density-mode
    # alignment promotes the whole group onto the structured kernel, so
    # the gate holds it at ONE signature (1.0 dispatches/round)
    struct_wls = ([by_name(n) for n in ("mm1", "mm8", "mm9", "mm10")] +
                  banded_attention_workloads()[:1])
    run_fleet("structured_cloud", ["sparsemap", "random_mapper"],
              struct_wls, "cloud")

    # device-resident fleet on the paper arch: the same ES searches fold
    # k=4 generations per device program (host_syncs_per_round tracks the
    # segment-phase sync ratio, gated at <= 1/k + prologue tolerance by
    # compare_sweep); sharded across every visible device when the host
    # exposes more than one (n_devices records it)
    from repro.launch.mesh import make_search_mesh
    # floor the budget so the run gets past the host-driven
    # calibration/HSHI prologue and into the segment phase (where
    # host_syncs_per_round is measured) even under --quick
    run_fleet("cloud_device_k4", ["sparsemap"], wls, "cloud",
              fleet_budget=max(budget, 2000),
              device_rounds=4, mesh=make_search_mesh())

    # the same fleet with the pipelined driver and compile-ahead both
    # disabled: the acceptance comparison for the pipelining PR —
    # cloud_device_k4's host_blocked_s must stay strictly below this
    # entry's, and its compile_ahead_misses must stay at the committed
    # baseline (0 = every round-1 signature predicted)
    run_fleet("cloud_device_k4_unpipelined", ["sparsemap"], wls, "cloud",
              fleet_budget=max(budget, 2000),
              device_rounds=4, mesh=make_search_mesh(),
              pipeline=False, compile_ahead=False)

    # search-as-a-service coalescing: one in-process sweep server serves
    # a single-client epoch, then TWO concurrent same-signature clients.
    # The pair epoch must hold 1.0 dispatches/round (both queries ride
    # one mega-batch), and its compile DELTA over the warm single-client
    # server is gated by compare_sweep like any arch entry (the honest
    # count: the pair's bigger stacked shape may cost one compile the
    # single-client fleet never needed; growing past the committed
    # baseline fails CI)
    import threading

    from repro.core import jax_cost as _jc
    from repro.launch import sweep_serve

    search.clear_cache()
    serve_budget = min(budget, 600)
    srv = sweep_serve.SweepServer(
        port=0, config=search.FleetConfig(device_rounds=1))
    srv.start_background()
    t0 = time.time()

    def serve_task(name, seed):
        return search.SearchTask(wls[0], "cloud", budget=serve_budget,
                                 seed=seed, name=name)

    try:
        list(sweep_serve.submit(srv.host, srv.port,
                                serve_task("serve_single", 0)))
        compiles_single = _jc.compilation_count()
        clients = [threading.Thread(
            target=lambda nm, sd: list(sweep_serve.submit(
                srv.host, srv.port, serve_task(nm, sd))),
            args=(f"serve_pair_{i}", i + 1)) for i in range(2)]
        for th in clients:
            th.start()
        for th in clients:
            th.join(timeout=600)
        st = next(iter(sweep_serve.request(srv.host, srv.port,
                                           {"op": "stats"})))["stats"]
    finally:
        srv.stop()
    fleet = st["fleet"]
    record["archs"].append(dict(
        arch="serve_coalesce", seconds=round(time.time() - t0, 2),
        budget=serve_budget,
        # compile DELTA of the concurrent-pair epoch over the warm
        # single-client server (0 = the pair rode existing programs)
        compiles=_jc.compilation_count() - compiles_single,
        rounds=fleet["rounds"], dispatches=fleet["dispatches"],
        dispatches_per_round=round(
            fleet["dispatches"] / max(fleet["rounds"], 1), 3),
        host_syncs_per_round=round(fleet["host_syncs_per_round"], 3),
        # largest same-signature group any epoch held (2 = the pair
        # provably coalesced; recorded, not gated — admission timing
        # can split the pair across epochs on a loaded machine)
        coalesced_group_size=max(
            (max(g.values()) for g in st["epoch_signature_groups"] if g),
            default=0),
        queries=st["queries"], completed=st["completed"],
        warm_started=st["warm_started"],
        pad_watermarks=fleet.get("pad_watermarks", {})))

    # contract-analysis provenance: lint wall-time + per-rule violation
    # counts, and the canonical jaxpr hash of every registered kernel
    # family (compare_sweep hard-fails recorded violations and surfaces
    # hash drift warn-only — an intentional kernel change moves hashes,
    # silent drift in an unrelated PR deserves a review look)
    from repro.analysis import run_report
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    roots = [p for p in (os.path.join(root, d)
                         for d in ("src", "benchmarks", "examples"))
             if os.path.isdir(p)]
    rep = run_report(roots=roots, include_jaxpr=True, include_scan=False)
    record["analysis"] = dict(
        lint_seconds=rep["lint"]["seconds"],
        jaxpr_seconds=rep["jaxpr"]["seconds"],
        rule_counts=rep["lint"]["rule_counts"],
        violations=(len(rep["lint"]["violations"])
                    + len(rep["jaxpr"]["findings"])))
    record["jaxpr_hashes"] = rep["jaxpr"]["hashes"]

    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def main(argv=None) -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=None)
    ap.add_argument("--quick", action="store_true",
                    help="minimal budgets (CI)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale budgets (20k evals/workload)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig2,fig7,fig17,fig18,"
                         "table_iv,roofline,arch_dse,es_ops,stacked_prep,"
                         "multisearch,method_sweep,device_rounds,"
                         "sweep_json")
    args = ap.parse_args(argv)

    budget = args.budget or (300 if args.quick else
                             20000 if args.full else 10000)
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import paper_tables, roofline

    def want(name):
        return only is None or name in only

    print("name,seconds,derived")

    if want("es_ops"):
        from benchmarks import es_ops
        t0 = time.time()
        ops = es_ops.bench_operators(pop_size=100)
        print(f"es_ops,{time.time()-t0:.1f},"
              f"mutate_speedup={ops['mutate_speedup']:.1f}x;"
              f"crossover_speedup={ops['crossover_speedup']:.1f}x;"
              f"combined_speedup={ops['speedup']:.1f}x")

    if want("device_rounds"):
        from benchmarks import es_ops
        t0 = time.time()
        dr = es_ops.bench_device_rounds(
            budget=min(max(budget, 1200), 2000))
        print(f"device_rounds,{time.time()-t0:.1f},"
              f"k={dr['device_rounds']};"
              f"fused_vs_host_speedup={dr['speedup']:.2f}x;"
              f"syncs_per_round={dr['fused_syncs_per_round']:.3f}"
              f"_vs_{dr['host_syncs_per_round']:.3f};"
              f"edp_exact={dr['edp_exact']}")

    if want("stacked_prep"):
        from benchmarks import es_ops
        t0 = time.time()
        sp = es_ops.bench_stacked_prep()
        print(f"stacked_prep,{time.time()-t0:.1f},"
              f"prep_speedup={sp['prep_speedup']:.1f}x;"
              f"round_ms={sp['eval_round_seconds']*1e3:.2f}")

    if want("sweep_json"):
        t0 = time.time()
        rec = bench_sweep_json(budget=min(budget, 1000))
        dpr = ";".join(f"{a['arch']}={a['dispatches_per_round']}"
                       for a in rec["archs"])
        print(f"sweep_json,{time.time()-t0:.1f},"
              f"path={SWEEP_JSON};dispatches_per_round={dpr}")

    if want("multisearch"):
        from benchmarks import es_ops
        t0 = time.time()
        ms = es_ops.bench_multisearch(budget=min(budget, 2000))
        print(f"multisearch,{time.time()-t0:.1f},"
              f"compiles={ms['multi_compiles']}_vs_seq_"
              f"{ms['seq_compiles']};edp_match={ms['edp_match']}")

    if want("method_sweep"):
        from benchmarks import es_ops
        t0 = time.time()
        sw = es_ops.bench_method_sweep(budget=min(budget, 2000))
        print(f"method_sweep,{time.time()-t0:.1f},"
              f"compiles={sw['sweep_compiles']}_vs_seq_"
              f"{sw['seq_compiles']};"
              f"dispatches_per_round={sw['dispatches_per_round']:.1f}"
              f"_vs_seq_{sw['seq_dispatches_per_round']:.1f};"
              f"edp_exact={sw['edp_exact']}")

    if want("fig2"):
        t0 = time.time()
        rows = paper_tables.fig2_interaction()
        # derived: does the best (mapping,fmt) change across densities?
        best = {}
        for r in rows:
            if not r["valid"]:
                continue
            key = r["density"]
            if key not in best or r["edp"] < best[key][1]:
                best[key] = ((r["mapping"], r["fmt"]), r["edp"])
        winners = {v[0] for v in best.values()}
        print(f"fig2_interaction,{time.time()-t0:.1f},"
              f"distinct_winners={len(winners)}")

    if want("fig7"):
        t0 = time.time()
        info = paper_tables.fig7_space(n_samples=1000)
        print(f"fig7_space,{time.time()-t0:.1f},"
              f"valid_frac={info['valid_frac']:.4f}")

    if want("fig17"):
        t0 = time.time()
        wl_names = ("conv2", "conv4") if args.quick else \
            ("conv2", "conv4", "conv5", "conv7")
        out = paper_tables.fig17_baselines(budget=budget,
                                           workload_names=wl_names)
        ours = {r["workload"]: r["edp"] for r in out
                if r["method"] == "sparsemap"}
        ratios = []
        for w, o in ours.items():
            b = min(r["edp"] for r in out
                    if r["workload"] == w and r["method"] != "sparsemap")
            if np.isfinite(b) and np.isfinite(o) and o > 0:
                ratios.append(b / o)
        gm = float(np.exp(np.mean(np.log(ratios)))) if ratios else 0.0
        print(f"fig17_baselines,{time.time()-t0:.1f},"
              f"geomean_best_baseline_over_ours={gm:.2f}x")

    if want("fig18"):
        t0 = time.time()
        out = paper_tables.fig18_ablation(budget=max(budget, 2000))
        summary = {(r['workload'], r['method']): r['best_edp']
                   for r in out}
        ok = all(
            summary[(w, 'sparsemap')] <= summary[(w, 'pfce_es')] * 1.5
            for w in ('mm3', 'conv4'))
        print(f"fig18_ablation,{time.time()-t0:.1f},ordering_holds={ok}")

    if want("table_iv"):
        t0 = time.time()
        wl_names = None
        if args.quick:
            wl_names = ["mm1", "mm3", "conv2", "conv4"]
        out = paper_tables.table_iv(budget=budget,
                                    workload_names=wl_names)
        sp = [r["speedup_vs_sparseloop"] for r in out
              if np.isfinite(r.get("speedup_vs_sparseloop", np.nan))]
        sg = [r["speedup_vs_sage"] for r in out
              if np.isfinite(r.get("speedup_vs_sage", np.nan))]
        gm_sp = float(np.exp(np.mean(np.log(np.maximum(sp, 1e-9))))) \
            if sp else 0.0
        gm_sg = float(np.exp(np.mean(np.log(np.maximum(sg, 1e-9))))) \
            if sg else 0.0
        print(f"table_iv,{time.time()-t0:.1f},"
              f"geomean_edp_reduction_vs_sparseloop={gm_sp:.2f}x;"
              f"vs_sage={gm_sg:.2f}x")

    if want("arch_dse"):
        t0 = time.time()
        from repro.configs.paper_workloads import arch_gemms
        from repro.core import search as search_lib
        rows = []
        for arch in ("mistral-nemo-12b", "kimi-k2-1t-a32b"):
            for wl in arch_gemms(arch)[:2]:
                res = search_lib.run("sparsemap", wl, "cloud",
                                     budget=budget, seed=0)
                rows.append((wl.name, res.best_edp))
        print(f"arch_dse,{time.time()-t0:.1f},"
              f"searched={len(rows)}_arch_gemms")

    if want("roofline"):
        t0 = time.time()
        recs = roofline.main()
        ok = sum(1 for r in recs if r.get("status") == "ok")
        print(f"roofline,{time.time()-t0:.1f},cells_ok={ok}")


if __name__ == "__main__":
    main()
