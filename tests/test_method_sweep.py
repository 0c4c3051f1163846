"""The method-agnostic concurrent sweep engine + its satellite bugfixes:

* evaluator cache keyed on workload CONTENT (id-reuse aliasing regression),
* `_Budget.register` budget-truncation semantics (NaN tail, not inf),
* explicit duplicate-task-name handling in `MultiSearch`,
* mixed-method `MultiSearch` == sequential `search.run` at fixed seeds,
* `stack_batches=True` (mega-batch dispatch) == `stack_batches=False`
  bit-for-bit, with strictly fewer compilations AND dispatches than the
  sequential equivalent,
* `jax_cost.eval_stacked` == per-model calls, bit-for-bit.
"""
import numpy as np
import pytest

from repro.configs.paper_workloads import by_name
from repro.core import jax_cost, search
from repro.core.evolution import _Budget
from repro.core.workload import spmm

METHODS = ["sparsemap", "pso", "random_mapper"]
WLS = ("mm1", "mm3")        # same (3, 16) natural signature
BUDGET = 300


# ------------------------------------------------- evaluator cache


def test_evaluator_cache_is_content_keyed_not_id_keyed():
    """Regression: the cache used id(workload); after gc a recycled id
    could return the WRONG (GenomeSpec, JaxCostModel).  Construct/drop
    workloads in a loop to provoke id reuse and check the evaluator always
    matches the live workload's content."""
    for i in range(25):
        m = 8 + 4 * (i % 7)
        wl = spmm("alias_probe", m, 16, 8, 0.5, 0.5)
        spec, ev = search.get_evaluator(wl, "cloud")
        assert spec.workload.dim_sizes == wl.dim_sizes, \
            f"cache aliased a stale workload at iteration {i}"
        assert ev.spec is spec
        del wl, spec, ev        # free the id for reuse


def test_evaluator_cache_shares_content_equal_workloads():
    a = spmm("same_wl", 16, 16, 16, 0.5, 0.5)
    b = spmm("same_wl", 16, 16, 16, 0.5, 0.5)
    assert a is not b and a.cache_key() == b.cache_key()
    assert search.get_evaluator(a, "cloud")[1] is \
        search.get_evaluator(b, "cloud")[1]
    # different content (density) must NOT share
    c = spmm("same_wl", 16, 16, 16, 0.5, 0.25)
    assert search.get_evaluator(c, "cloud")[1] is not \
        search.get_evaluator(a, "cloud")[1]


# ------------------------------------------------- budget truncation


def test_budget_truncation_marks_tail_nan():
    """End-of-budget behavior: only the evaluated prefix is counted; the
    truncated tail comes back NaN (not inf), so selection can tell
    "not evaluated" from "evaluated and invalid"."""
    tr = _Budget(6)
    genomes = np.arange(20).reshape(10, 2)
    out = dict(edp=np.full(10, 2.0), valid=np.ones(10, bool))
    edp = tr.register(genomes, out)
    assert tr.last_n == 6 and tr.evals == 6 == len(tr.hist)
    assert tr.valid == 6
    np.testing.assert_array_equal(edp[:6], 2.0)
    assert np.isnan(edp[6:]).all()
    assert tr.exhausted
    # a post-exhaustion batch is all-NaN and counts nothing
    edp2 = tr.register(genomes, out)
    assert tr.last_n == 0 and tr.evals == 6
    assert np.isnan(edp2).all()
    # NaN rows sort after real rows and compare False, like inf rows
    order = np.argsort(edp)
    assert set(order[:6]) == set(range(6))


def test_budget_truncation_tail_never_becomes_best():
    tr = _Budget(2)
    genomes = np.zeros((4, 3), dtype=np.int64)
    out = dict(edp=np.array([9.0, 8.0, 1.0, 0.5]),
               valid=np.ones(4, bool))
    tr.register(genomes, out)
    assert tr.best == 8.0           # rows 2,3 were beyond the budget
    assert tr.evals == 2


# ------------------------------------------------- duplicate names


def test_multisearch_duplicate_names_all_suffixed():
    wl = by_name("mm1")
    ms = search.MultiSearch([
        search.SearchTask(wl, "cloud", budget=50, name="dup"),
        search.SearchTask(wl, "cloud", budget=50, name="dup"),
        search.SearchTask(wl, "cloud", budget=50, name="solo"),
        search.SearchTask(wl, "cloud", budget=50, name="dup"),
    ])
    assert ms.final_names == ["dup#0", "dup#1", "solo", "dup#2"]
    res = ms.run()
    assert set(res) == {"dup#0", "dup#1", "solo", "dup#2"}


def test_multisearch_suffixes_avoid_explicit_names():
    """An auto-suffix must never collide with a name another task chose
    explicitly — no two tasks ever share a results key."""
    wl = by_name("mm1")
    ms = search.MultiSearch([
        search.SearchTask(wl, "cloud", budget=50, name="dup"),
        search.SearchTask(wl, "cloud", budget=50, name="dup"),
        search.SearchTask(wl, "cloud", budget=50, name="dup#0"),
    ])
    assert ms.final_names == ["dup#1", "dup#2", "dup#0"]
    assert len(set(ms.final_names)) == len(ms.final_names)


def test_multisearch_default_names_include_method():
    wl = by_name("mm1")
    ms = search.MultiSearch([
        search.SearchTask(wl, "cloud", budget=50),
        search.SearchTask(wl, "cloud", budget=50, method="pso"),
    ])
    assert ms.final_names == ["mm1@cloud", "pso:mm1@cloud"]


def test_searchtask_rejects_method_without_request_generator():
    with pytest.raises(KeyError):
        search.SearchTask(by_name("mm1"), method="no_such_method")


def test_standard_es_joins_the_fleet():
    """standard_es (direct encoding) now has a request generator over
    canonical rows: a MultiSearch task with it matches the sequential
    closed-form run exactly at a fixed seed."""
    wl = by_name("mm1")
    seq = search.run("standard_es", wl, "cloud", budget=200, seed=5)
    ms = search.MultiSearch([search.SearchTask(
        wl, "cloud", budget=200, seed=5, method="standard_es")])
    (res,) = ms.run().values()
    assert res.evals == seq.evals == 200
    assert res.best_edp == seq.best_edp
    np.testing.assert_array_equal(res.history, seq.history)


def test_run_method_sweep_rejects_grid_collisions():
    """The {method: {workload_name: ...}} grid cannot represent duplicate
    methods or duplicate workload names — refuse instead of silently
    dropping one search's result."""
    a = spmm("twin", 16, 16, 16, 0.5, 0.5)
    b = spmm("twin", 32, 16, 16, 0.5, 0.5)
    with pytest.raises(ValueError):
        search.run_method_sweep(["pso"], [a, b], budget=50)
    with pytest.raises(ValueError):
        search.run_method_sweep(["pso", "pso"], [a], budget=50)


# ------------------------------------------------- stacked evaluator


def test_eval_stacked_bitexact_vs_per_model_calls():
    a = spmm("stk_a", 32, 64, 48, 0.2, 0.5)
    b = spmm("stk_b", 48, 32, 64, 0.4, 0.3)
    sa, eva = search.get_evaluator(a, "cloud")
    sb, evb = search.get_evaluator(b, "edge")
    assert eva.signature == evb.signature
    rng = np.random.default_rng(0)
    ga, gb = sa.random_genomes(rng, 37), sb.random_genomes(rng, 50)
    ra, rb = eva(ga), evb(gb)
    oa, ob = jax_cost.eval_stacked([eva, evb], [ga, gb])
    for k in ra:
        np.testing.assert_array_equal(np.asarray(ra[k]), np.asarray(oa[k]))
        np.testing.assert_array_equal(np.asarray(rb[k]), np.asarray(ob[k]))
    # pad_floor (the sticky mega-batch shape) must not change results
    (oa2,) = jax_cost.eval_stacked([eva], [ga], pad_floor=512)
    for k in ra:
        np.testing.assert_array_equal(np.asarray(ra[k]),
                                      np.asarray(oa2[k]))


def test_eval_stacked_caches_tiled_constants_per_fleet_epoch():
    """The per-task constants table on the device is uploaded again only
    when the group's set of tasks changes — repeated rounds of a steady
    fleet reuse it, and reusing rounds stay bit-identical to uploading
    ones."""
    a = spmm("prep_a", 32, 64, 48, 0.2, 0.5)
    b = spmm("prep_b", 48, 32, 64, 0.4, 0.3)
    sa, eva = search.get_evaluator(a, "cloud")
    sb, evb = search.get_evaluator(b, "edge")
    rng = np.random.default_rng(2)
    ga, gb = sa.random_genomes(rng, 37), sb.random_genomes(rng, 50)
    jax_cost.reset_stack_prep_counts()
    first = jax_cost.eval_stacked([eva, evb], [ga, gb])
    again = jax_cost.eval_stacked([eva, evb], [ga, gb])
    hits, misses = jax_cost.stack_prep_counts()
    assert (hits, misses) == (1, 1)
    for x, y in zip(first, again):
        for k in x:
            np.testing.assert_array_equal(np.asarray(x[k]),
                                          np.asarray(y[k]))
    # a different fleet shape is a new epoch: rebuild, then warm again
    jax_cost.eval_stacked([eva], [ga])
    jax_cost.eval_stacked([eva], [ga])
    hits, misses = jax_cost.stack_prep_counts()
    assert (hits, misses) == (2, 2)


def test_eval_stacked_rejects_mixed_signatures():
    a = spmm("sig_a", 32, 64, 48, 0.2, 0.5)        # bucket 16
    c = spmm("sig_c", 128, 256, 512, 0.1, 0.9)     # bucket 32
    sa, eva = search.get_evaluator(a, "cloud")
    sc, evc = search.get_evaluator(c, "cloud")
    assert eva.signature != evc.signature
    rng = np.random.default_rng(1)
    with pytest.raises(ValueError):
        jax_cost.eval_stacked([eva, evc],
                              [sa.random_genomes(rng, 8),
                               sc.random_genomes(rng, 8)])


# ------------------------------------------------- mixed-method fleet


@pytest.fixture(scope="module")
def sweep_runs():
    """One shared (sequential, stacked, unstacked) run triple: sequential
    `search.run` per (method, workload), then the same grid through
    `MultiSearch` with and without mega-batch stacking, each from a cold
    compile cache so compilation/dispatch counts are comparable."""
    wls = [by_name(n) for n in WLS]
    search.clear_cache()
    seq = {m: {w.name: search.run(m, w, "cloud", budget=BUDGET, seed=0)
               for w in wls} for m in METHODS}
    seq_counts = (jax_cost.compilation_count(), jax_cost.dispatch_count())

    search.clear_cache()
    stacked_stats: dict = {}
    stacked = search.run_method_sweep(METHODS, wls, "cloud", budget=BUDGET,
                                      seed=0, stack_batches=True,
                                      stats_out=stacked_stats)
    stacked_counts = (jax_cost.compilation_count(),
                      stacked_stats["dispatches"])

    search.clear_cache()
    unstacked_stats: dict = {}
    unstacked = search.run_method_sweep(METHODS, wls, "cloud",
                                        budget=BUDGET, seed=0,
                                        stack_batches=False,
                                        stats_out=unstacked_stats)
    return dict(seq=seq, stacked=stacked, unstacked=unstacked,
                seq_counts=seq_counts, stacked_counts=stacked_counts,
                stacked_stats=stacked_stats, unstacked_stats=unstacked_stats)


def test_mixed_method_fleet_matches_sequential_exactly(sweep_runs):
    for m in METHODS:
        for w in WLS:
            a = sweep_runs["seq"][m][w]
            b = sweep_runs["stacked"][m][w]
            assert a.best_edp == b.best_edp, (m, w)
            assert a.evals == b.evals == BUDGET, (m, w)
            assert a.valid_evals == b.valid_evals, (m, w)
            np.testing.assert_array_equal(a.history, b.history,
                                          err_msg=f"{m}/{w}")
            if a.best_genome is not None:
                np.testing.assert_array_equal(a.best_genome, b.best_genome)


def test_stacked_matches_unstacked_bit_for_bit(sweep_runs):
    for m in METHODS:
        for w in WLS:
            a = sweep_runs["unstacked"][m][w]
            b = sweep_runs["stacked"][m][w]
            assert a.best_edp == b.best_edp, (m, w)
            np.testing.assert_array_equal(a.history, b.history,
                                          err_msg=f"{m}/{w}")


def test_stacked_sweep_fewer_compiles_and_dispatches(sweep_runs):
    seq_compiles, seq_dispatches = sweep_runs["seq_counts"]
    st_compiles, st_dispatches = sweep_runs["stacked_counts"]
    assert st_compiles < seq_compiles
    assert st_dispatches < seq_dispatches
    # one shared signature (mm1/mm3 align; default topology), so one
    # dispatch per round
    from repro.core.arch import ARCH_SPARSEMAP
    stats = sweep_runs["stacked_stats"]
    assert stats["signatures"] == \
        [(3, 16, ARCH_SPARSEMAP.topology.fingerprint, "u")]
    assert stats["dispatches"] == stats["rounds"]
    # unstacked pays one dispatch per alive task per round
    assert stats["dispatches"] < sweep_runs["unstacked_stats"]["dispatches"]


def test_run_method_sweep_grid_shape(sweep_runs):
    grid = sweep_runs["stacked"]
    assert sorted(grid) == sorted(METHODS)
    for m in METHODS:
        assert sorted(grid[m]) == sorted(WLS)
        for w in WLS:
            assert grid[m][w].extras["method"] == m
