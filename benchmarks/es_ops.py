"""ES operator micro-benchmark + MultiSearch compilation-sharing checks.

Benchmarks backing the vectorized/concurrent-engine claims:

* ``bench_operators`` — throughput (individuals/s) of the vectorized
  ``mutate`` + ``crossover`` (and HSHI round sampling / best-so-far
  tracking) vs the seed per-individual Python loops, at the paper's
  pop_size=100 on a paper workload genome.
* ``bench_multisearch`` — a 2-workload sweep through ``MultiSearch``
  must perform FEWER XLA compilations than sequential ``search.run``
  calls (signature alignment) while matching their best-EDP results.
* ``bench_method_sweep`` — a 2-workload x 3-method fig17-style grid via
  ``run_method_sweep(stack_batches=True)`` must perform strictly fewer
  XLA compilations AND fewer device dispatches per round (one padded
  mega-batch per signature) than the sequential equivalent, while
  matching sequential per-method best-EDP exactly at fixed seeds.
* ``bench_stacked_prep`` — dispatch-prep time of the mega-batch path:
  the per-(fleet, signature)-epoch constants cache vs rebuilding the
  tiled per-row constants (broadcast_to + concat) every round.

    PYTHONPATH=src python -m benchmarks.es_ops
    PYTHONPATH=src python -m benchmarks.run --only es_ops,multisearch,method_sweep
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np


# ---------------------------------------------------- seed reference ops


def _ref_mutate(genomes, spec, rng, p_mut, genes_per, sens, p_high):
    out = genomes.copy()
    L = spec.length
    for i in range(len(out)):
        if rng.random() >= p_mut:
            continue
        if sens is not None:
            seg = sens.high_indices if rng.random() < p_high \
                else sens.low_indices
            if len(seg) == 0:
                seg = np.arange(L)
        else:
            seg = np.arange(L)
        for _ in range(genes_per):
            g = int(seg[rng.integers(0, len(seg))])
            out[i, g] = rng.integers(0, spec.gene_ub[g])
    return out


def _ref_crossover(parents, n_children, spec, rng, sens):
    L = spec.length
    if sens is not None:
        pts = {0, L}
        for a, b in sens.high_segments():
            pts.add(a)
            pts.add(b)
        cut_points = sorted(pts - {0, L}) or [L // 2]
    else:
        cut_points = list(range(1, L))
    kids = np.empty((n_children, L), dtype=parents.dtype)
    for i in range(n_children):
        a, b = rng.integers(0, len(parents), 2)
        cut = cut_points[rng.integers(0, len(cut_points))]
        kids[i, :cut] = parents[a, :cut]
        kids[i, cut:] = parents[b, cut:]
    return kids


def _time(fn, min_seconds: float = 0.4) -> float:
    """Calls/second of fn()."""
    fn()                                    # warmup
    n = 0
    t0 = time.perf_counter()
    while True:
        fn()
        n += 1
        dt = time.perf_counter() - t0
        if dt >= min_seconds:
            return n / dt


def bench_operators(pop_size: int = 100, workload_name: str = "mm3"
                    ) -> Dict[str, float]:
    from repro.configs.paper_workloads import by_name
    from repro.core.encoding import GenomeSpec
    from repro.core.evolution import crossover, mutate
    from repro.core.sensitivity import SensitivityResult

    wl = by_name(workload_name)
    spec = GenomeSpec(wl)
    high = np.zeros(spec.length, dtype=bool)
    high[spec.segments["perm"].slice] = True
    high[spec.segments["sg"].slice] = True
    sens = SensitivityResult(
        scores=high.astype(np.float64), high_mask=high,
        valid_pool=spec.random_genomes(np.random.default_rng(0), 64),
        threshold=0.75, evals_used=0)

    rng = np.random.default_rng(1)
    pop = spec.random_genomes(rng, pop_size)
    parents = pop[:40]

    def vec_pair():
        kids = crossover(parents, pop_size, spec, rng, sens)
        mutate(kids, spec, rng, 0.9, 2, sens, 0.5)

    def ref_pair():
        kids = _ref_crossover(parents, pop_size, spec, rng, sens)
        _ref_mutate(kids, spec, rng, 0.9, 2, sens, 0.5)

    vec_cps = _time(vec_pair)
    ref_cps = _time(ref_pair)
    out = dict(
        workload=workload_name, pop_size=pop_size, genome_len=spec.length,
        vectorized_pairs_per_s=vec_cps * pop_size,
        reference_pairs_per_s=ref_cps * pop_size,
        speedup=vec_cps / ref_cps)

    # individual operators, for the breakdown
    out["mutate_speedup"] = (
        _time(lambda: mutate(pop, spec, rng, 0.9, 2, sens, 0.5)) /
        _time(lambda: _ref_mutate(pop, spec, rng, 0.9, 2, sens, 0.5)))
    out["crossover_speedup"] = (
        _time(lambda: crossover(parents, pop_size, spec, rng, sens)) /
        _time(lambda: _ref_crossover(parents, pop_size, spec, rng, sens)))
    return out


def bench_stacked_prep(n_tasks: int = 6, rows_per_task: int = 64,
                       rounds: int = 50) -> Dict[str, float]:
    """Dispatch-prep micro-benchmark for the mega-batch path: time per
    ``eval_stacked`` constants prep when the signature's device-resident
    per-task table is reused vs when it is built and uploaded again (what
    an admission or a retirement costs)."""
    from repro.configs.paper_workloads import by_name
    from repro.core import jax_cost, search
    from repro.core.jax_cost import _stack_table

    wls = [by_name(n) for n in ("mm1", "mm3")]
    models, batches = [], []
    rng = np.random.default_rng(0)
    for i in range(n_tasks):
        spec, ev = search.get_evaluator(wls[i % len(wls)], "cloud")
        models.append(ev)
        batches.append(spec.random_genomes(rng, rows_per_task))

    def cached():
        return _stack_table(models)

    def uncached():
        jax_cost._STACK_TABLES.clear()
        return _stack_table(models)

    cached()                                    # upload the table
    cached_cps = _time(cached)
    uncached_cps = _time(uncached)

    # end-to-end: full eval_stacked rounds on a steady fleet
    jax_cost.reset_stack_prep_counts()
    t0 = time.perf_counter()
    for _ in range(rounds):
        jax_cost.eval_stacked(models, batches)
    per_round_s = (time.perf_counter() - t0) / rounds
    hits, misses = jax_cost.stack_prep_counts()
    return dict(
        n_tasks=n_tasks, rows_per_task=rows_per_task,
        cached_preps_per_s=cached_cps, uncached_preps_per_s=uncached_cps,
        prep_speedup=cached_cps / uncached_cps,
        eval_round_seconds=per_round_s,
        prep_hits=hits, prep_misses=misses)


def bench_device_rounds(budget: int = 2000, seed: int = 0,
                        device_rounds: int = 8) -> Dict[str, float]:
    """Fused-round vs host-loop throughput: the SAME pre-drawn operator
    plans executed as one vmap-of-``lax.scan`` device program every k
    generations (``device_execute=True``) vs replayed per-round on the
    host (``device_execute=False``, one dispatch + one sync per
    generation).  Results are bit-identical by construction, so the
    deltas are pure host-sync/dispatch overhead."""
    from repro.configs.paper_workloads import by_name
    from repro.core import jax_cost, search

    wls = [by_name("mm1"), by_name("mm3")]

    def fleet(execute: bool):
        search.clear_cache()
        stats: Dict = {}
        t0 = time.perf_counter()
        grid = search.run_method_sweep(
            ["sparsemap"], wls, "cloud", budget=budget, seed=seed,
            stack_batches=True, device_rounds=device_rounds,
            device_execute=execute, stats_out=stats)
        dt = time.perf_counter() - t0
        best = {w.name: grid["sparsemap"][w.name].best_edp for w in wls}
        return dt, stats, best

    fused_s, fused_stats, fused_best = fleet(True)
    host_s, host_stats, host_best = fleet(False)
    return dict(
        budget=budget, device_rounds=device_rounds,
        fused_seconds=fused_s, host_seconds=host_s,
        speedup=host_s / fused_s,
        fused_host_syncs=fused_stats["host_syncs"],
        host_host_syncs=host_stats["host_syncs"],
        fused_syncs_per_round=fused_stats["host_syncs_per_round"],
        host_syncs_per_round=host_stats["host_syncs_per_round"],
        fused_dispatches=fused_stats["dispatches"],
        host_dispatches=host_stats["dispatches"],
        edp_exact=all(fused_best[w] == host_best[w] for w in fused_best))


def bench_multisearch(budget: int = 1000, seed: int = 0
                      ) -> Dict[str, float]:
    from repro.configs.paper_workloads import by_name
    from repro.core import jax_cost, search

    # mm1 (prime bucket 16) and mm4 (bucket 32): two natural signatures
    wls = [by_name("mm1"), by_name("mm4")]

    search.clear_cache()
    t0 = time.perf_counter()
    seq = {w.name: search.run("sparsemap", w, "cloud", budget=budget,
                              seed=seed) for w in wls}
    seq_s = time.perf_counter() - t0
    seq_compiles = jax_cost.compilation_count()

    search.clear_cache()
    t0 = time.perf_counter()
    ms = search.MultiSearch(
        [search.SearchTask(w, "cloud", budget=budget, seed=seed)
         for w in wls])
    multi = ms.run()
    multi_s = time.perf_counter() - t0
    multi_compiles = jax_cost.compilation_count()

    match = all(
        (not np.isfinite(seq[w.name].best_edp)) or
        abs(np.log10(multi[f"{w.name}@cloud"].best_edp) -
            np.log10(seq[w.name].best_edp)) < 1e-3
        for w in wls)
    return dict(
        budget=budget, seq_compiles=seq_compiles,
        multi_compiles=multi_compiles, seq_seconds=seq_s,
        multi_seconds=multi_s, edp_match=match,
        signatures=ms.stats["signatures"],
        natural_signatures=ms.stats["natural_signatures"])


def bench_method_sweep(budget: int = 2000, seed: int = 0
                       ) -> Dict[str, float]:
    """Sequential fig17-style grid vs one stacked MultiSearch fleet:
    compilations, device dispatches, wall-clock, and exact result parity."""
    from repro.configs.paper_workloads import by_name
    from repro.core import jax_cost, search

    wls = [by_name("mm1"), by_name("mm3")]      # shared (3, 16) signature
    methods = ["sparsemap", "pso", "random_mapper"]

    search.clear_cache()
    t0 = time.perf_counter()
    seq = {m: {w.name: search.run(m, w, "cloud", budget=budget, seed=seed)
               for w in wls} for m in methods}
    seq_s = time.perf_counter() - t0
    seq_compiles = jax_cost.compilation_count()
    seq_dispatches = jax_cost.dispatch_count()

    search.clear_cache()
    stats: Dict = {}
    t0 = time.perf_counter()
    grid = search.run_method_sweep(methods, wls, "cloud", budget=budget,
                                   seed=seed, stack_batches=True,
                                   stats_out=stats)
    sweep_s = time.perf_counter() - t0
    sweep_compiles = jax_cost.compilation_count()

    exact = all(
        seq[m][w.name].best_edp == grid[m][w.name].best_edp and
        np.array_equal(seq[m][w.name].history, grid[m][w.name].history)
        for m in methods for w in wls)
    return dict(
        budget=budget, n_methods=len(methods), n_workloads=len(wls),
        seq_compiles=seq_compiles, sweep_compiles=sweep_compiles,
        seq_dispatches=seq_dispatches, sweep_dispatches=stats["dispatches"],
        rounds=stats["rounds"],
        dispatches_per_round=stats["dispatches"] / max(stats["rounds"], 1),
        seq_dispatches_per_round=seq_dispatches / max(stats["rounds"], 1),
        seq_seconds=seq_s, sweep_seconds=sweep_s, edp_exact=exact)


def main() -> None:
    ops = bench_operators()
    print(f"es_ops: pop={ops['pop_size']} L={ops['genome_len']} "
          f"({ops['workload']}) — mutate {ops['mutate_speedup']:.1f}x, "
          f"crossover {ops['crossover_speedup']:.1f}x, "
          f"mutate+crossover {ops['speedup']:.1f}x "
          f"({ops['vectorized_pairs_per_s']:.3g} vs "
          f"{ops['reference_pairs_per_s']:.3g} individuals/s)")
    sp = bench_stacked_prep()
    print(f"stacked_prep: {sp['n_tasks']} tasks x {sp['rows_per_task']} "
          f"rows — cached prep {sp['prep_speedup']:.1f}x faster "
          f"({sp['cached_preps_per_s']:.3g} vs "
          f"{sp['uncached_preps_per_s']:.3g} preps/s), steady round "
          f"{sp['eval_round_seconds'] * 1e3:.2f}ms, "
          f"constants table reuses/uploads "
          f"{sp['prep_hits']}/{sp['prep_misses']}")
    ms = bench_multisearch()
    print(f"multisearch: compiles {ms['multi_compiles']} vs sequential "
          f"{ms['seq_compiles']}, signatures {ms['signatures']} vs "
          f"{ms['natural_signatures']}, edp_match={ms['edp_match']}, "
          f"{ms['multi_seconds']:.1f}s vs {ms['seq_seconds']:.1f}s")
    dr = bench_device_rounds()
    print(f"device_rounds: k={dr['device_rounds']} — fused "
          f"{dr['fused_seconds']:.1f}s vs host-loop "
          f"{dr['host_seconds']:.1f}s ({dr['speedup']:.2f}x), syncs "
          f"{dr['fused_host_syncs']} vs {dr['host_host_syncs']} "
          f"({dr['fused_syncs_per_round']:.3f} vs "
          f"{dr['host_syncs_per_round']:.3f} per round), "
          f"edp_exact={dr['edp_exact']}")
    sw = bench_method_sweep()
    print(f"method_sweep: {sw['n_workloads']} workloads x "
          f"{sw['n_methods']} methods — compiles {sw['sweep_compiles']} vs "
          f"sequential {sw['seq_compiles']}, dispatches "
          f"{sw['sweep_dispatches']} vs {sw['seq_dispatches']} "
          f"({sw['dispatches_per_round']:.1f} vs "
          f"{sw['seq_dispatches_per_round']:.1f} per round), "
          f"edp_exact={sw['edp_exact']}, "
          f"{sw['sweep_seconds']:.1f}s vs {sw['seq_seconds']:.1f}s")


if __name__ == "__main__":
    main()
