"""Pipelined fleet rounds (COMPAT.md "Pipelined dispatch contract"):

* pipeline=True vs pipeline=False must be BIT-IDENTICAL — the pipelined
  driver defers harvests/finalizes one round late, but dispatch shapes,
  registration order and values are the same by construction;
* the in-scan direct-genome translation (``standard_es`` segments) must
  match the numpy oracle (``DirectValueSpec.to_canonical``) row for row,
  including untranslatable rows;
* ``stagnation_restart > 0`` no longer forces the per-round path: the
  folded restart branch matches its host replay bit-for-bit and keeps
  the 1/k host-sync ratio;
* the compile-ahead AOT registry counts hits/misses correctly and the
  ``jax_cost`` module counters survive a two-thread hammer;
* the per-backend ``device_rounds`` chooser resolves and records its
  provenance.
"""
import threading

import numpy as np
import pytest

from repro.configs.paper_workloads import by_name, structured_workloads
from repro.core import es_ops, jax_cost, search
from repro.core.direct_encoding import DirectValueSpec
from repro.core.es_ops import DeviceSegment
from repro.core.workload import spmm

BUDGET = 700
SEED = 3
K = 4
#: a workload on another signature than mm2/mm3's (prime bucket 32)
OTHER_SIG = spmm("other_sig", 128, 256, 512, 0.1, 0.9)


def _grid_equal(a, b):
    assert set(a) == set(b)
    for m in a:
        assert set(a[m]) == set(b[m])
        for w in a[m]:
            ra, rb = a[m][w], b[m][w]
            assert ra.best_edp == rb.best_edp, (m, w)
            assert np.array_equal(ra.history, rb.history), (m, w)
            assert ra.evals == rb.evals and \
                ra.valid_evals == rb.valid_evals, (m, w)


def _sweep(pipeline, compile_ahead=True, device_rounds=K, stats=None):
    """Mixed-method, mixed-density fleet: a segmented ES, the segmented
    direct-encoding ES, and a per-round baseline, over a uniform and a
    structured-density workload."""
    wls = [by_name("mm1"), structured_workloads()[0]]
    return search.run_method_sweep(
        ["sparsemap", "standard_es", "pso"], wls, "cloud",
        budget=BUDGET, seed=SEED,
        device_rounds=device_rounds, pipeline=pipeline,
        compile_ahead=compile_ahead, stats_out=stats)


def test_pipelined_equals_unpipelined_bitforbit():
    stats_on, stats_off = {}, {}
    on = _sweep(pipeline=True, stats=stats_on)
    off = _sweep(pipeline=False, stats=stats_off)
    _grid_equal(on, off)
    assert stats_on["pipeline"] and not stats_off["pipeline"]
    # both drivers issue the same device dispatches
    assert stats_on["dispatches"] == stats_off["dispatches"]


def test_pipeline_off_matches_no_compile_ahead():
    """Compile-ahead only changes WHERE compilation happens, never what
    is computed."""
    _grid_equal(_sweep(pipeline=True, compile_ahead=True),
                _sweep(pipeline=False, compile_ahead=False))


# ------------------------------------------------ direct translation


def _identity_segment(spec, dspec, pop, edp):
    """A 1-generation direct segment whose kids are exactly
    ``pop[:B-1]``: fitness is pre-sorted (stable order = identity),
    every child crosses parent i with itself, mutation inactive."""
    B = len(pop)
    C = B - 1
    d = es_ops.GenDraws(
        ab=np.stack([np.arange(C)] * 2, axis=1),
        cuts=np.ones(C, dtype=np.int64),
        active=np.zeros(C, dtype=bool),
        gene=np.zeros((C, 2), dtype=np.int64),
        vals=np.zeros((C, 2), dtype=np.int64))
    aux = dict(
        scramble=np.asarray(dspec.scramble, dtype=np.int32),
        dim_sizes=np.asarray(
            [dspec.workload.dim_sizes[k] for k in dspec.workload.dim_order],
            dtype=np.float32))
    return DeviceSegment(spec=spec, pop=pop, edp=edp, rounds=1, gen0=0,
                         n_parents=C, n_elite=1, genes_per=2, draws=
                         es_ops.stack_draws([d]), kind="direct", aux=aux)


def test_direct_translation_matches_numpy_oracle():
    wl = by_name("mm1")
    spec, ev = search.get_evaluator(wl, "cloud")
    dspec = DirectValueSpec(spec)
    rng = np.random.default_rng(7)
    pop = dspec.random_genomes(rng, 33)
    # guarantee translatable rows: trivial and two-way factor splits
    nl = dspec.n_levels
    for i, split in enumerate([(0,), (1,), (0, 1)]):
        row = pop[i]
        col = dspec.fact_sl.start
        for dim in dspec.workload.dim_order:
            size = dspec.workload.dim_sizes[dim]
            facs = [1] * nl
            if len(split) == 1 or len(dspec.div[dim]) < 3:
                facs[split[0] % nl] = size
            else:
                a = dspec.div[dim][1]       # smallest divisor > 1
                facs[0], facs[1] = a, size // a
            row[col:col + nl] = facs
            col += nl
    edp = np.arange(len(pop), dtype=np.float32)  # pre-sorted fitness
    seg = _identity_segment(spec, dspec, pop, edp)
    res = jax_cost.run_segments([ev], [seg])[0]
    kids_canon, out = res.gens[0]
    n_valid = 0
    for i in range(len(pop) - 1):
        oracle = dspec.to_canonical(pop[i])
        if oracle is None:
            assert not out["valid"][i], i
            assert np.array_equal(kids_canon[i],
                                  np.zeros(spec.length, np.int64)), i
            assert not np.isfinite(out["edp"][i]), i
        else:
            n_valid += 1
            assert np.array_equal(kids_canon[i], oracle), i
    assert n_valid >= 3      # the crafted rows did translate


def test_standard_es_segments_match_host_loop():
    """Device-executed direct segments == the host replay of the same
    plans, bit for bit (the ``standard_es`` exact-parity acceptance)."""
    wls = [by_name("mm1")]

    def go(device_execute):
        return search.run_method_sweep(
            ["standard_es"], wls, "cloud", budget=BUDGET, seed=SEED,
            device_rounds=K,
            device_execute=device_execute)

    _grid_equal(go(True), go(False))


# ------------------------------------------------ restart in-scan


def test_restart_segment_matches_host_replay():
    wls = [by_name("mm1")]
    kw = {"sparsemap": dict(stagnation_restart=2)}

    def go(device_execute, stats):
        return search.run_method_sweep(
            ["sparsemap"], wls, "cloud", budget=BUDGET, seed=SEED,
            device_rounds=K,
            device_execute=device_execute, method_kw=kw, stats_out=stats)

    sa, sb = {}, {}
    _grid_equal(go(True, sa), go(False, sb))
    # restart no longer forces the per-round path: the device fleet's
    # steady-state host-sync ratio is 1/k
    assert sa["host_syncs_per_round"] == pytest.approx(1.0 / K)


# ------------------------------------------------ compile-ahead


def test_compile_ahead_hits_and_misses():
    wl = by_name("mm2")
    jax_cost.clear_compile_cache()
    search._CACHE.clear()
    spec, ev = search.get_evaluator(wl, "cloud")
    jax_cost.reset_compile_ahead_counts()
    jax_cost.compile_ahead([jax_cost.stacked_compile_job(ev, 64)],
                           wait=True)
    rng = np.random.default_rng(0)
    ev(spec.random_genomes(rng, 10))        # pads to 64 -> AOT hit
    assert jax_cost.compile_ahead_counts() == (1, 0)
    ev(spec.random_genomes(rng, 100))       # pads to 128 -> fresh trace
    assert jax_cost.compile_ahead_counts() == (1, 1)
    ev(spec.random_genomes(rng, 90))        # 128 again: warm jit, no miss
    assert jax_cost.compile_ahead_counts() == (1, 1)
    assert jax_cost.compilation_count() >= 2


def test_unclaimed_families_never_count_misses():
    wl = by_name("mm3")
    jax_cost.clear_compile_cache()
    search._CACHE.clear()
    spec, ev = search.get_evaluator(wl, "cloud")
    _, other = search.get_evaluator(OTHER_SIG, "cloud")
    assert other.signature != ev.signature
    jax_cost.reset_compile_ahead_counts()
    # compile-ahead runs for an unrelated family only
    jax_cost.compile_ahead([jax_cost.stacked_compile_job(other, 256)],
                           wait=True)
    rng = np.random.default_rng(0)
    ev(spec.random_genomes(rng, 10))        # mm3's family unclaimed
    assert jax_cost.compile_ahead_counts() == (0, 0)


def test_compile_ahead_failures_are_counted_not_swallowed():
    """A background compile that raises and an AOT executable that
    raises when called are both counted as compile-ahead errors (the
    first one kept); the failing call reaches its caller."""
    import jax
    jax_cost.clear_compile_cache()

    def broken(x):
        raise ValueError("forced lowering failure")

    key = (3, 16, "forced", "u", "stacked", 4)
    job = (key, jax.jit(broken), (jax.ShapeDtypeStruct((4,), np.float32),))
    jax_cost.compile_ahead([job], wait=True)
    n, first = jax_cost.compile_ahead_errors()
    assert n == 1 and "forced lowering failure" in first

    def raising_executable(*args):
        raise RuntimeError("forced execution failure")

    with jax_cost._LOCK:
        jax_cost._AOT_FNS[key] = raising_executable
    with pytest.raises(RuntimeError, match="forced execution failure"):
        jax_cost._aot_call(key, jax.jit(lambda x: x), (np.zeros(4),))
    n, first = jax_cost.compile_ahead_errors()
    assert n == 2 and "forced lowering failure" in first
    assert jax_cost.compile_ahead_counts() == (0, 0)
    jax_cost.clear_compile_cache()
    assert jax_cost.compile_ahead_errors() == (0, None)


def test_compile_ahead_claims_are_per_pass():
    """A fleet's misses are counted against its own predictions: a
    family only an EARLIER pass claimed does not arm the counter."""
    wl = by_name("mm3")
    jax_cost.clear_compile_cache()
    search._CACHE.clear()
    spec, ev = search.get_evaluator(wl, "cloud")
    _, other = search.get_evaluator(OTHER_SIG, "cloud")
    jax_cost.compile_ahead([jax_cost.stacked_compile_job(ev, 64)],
                           wait=True)
    jax_cost.compile_ahead([jax_cost.stacked_compile_job(other, 256)],
                           wait=True)
    jax_cost.reset_compile_ahead_counts()
    ev(spec.random_genomes(np.random.default_rng(0), 100))  # mm3's, 128
    assert jax_cost.compile_ahead_counts() == (0, 0)


def test_new_compile_ahead_pass_requeues_what_a_cancelled_pass_held():
    """A fleet that ends cancels its compile-ahead queue.  A key that
    queue still held pending when the next fleet's pass starts must be
    compiled by the new pass, not skipped as pending and then dropped
    by the cancelled worker (its dispatch would trace inline: a miss)."""
    import jax
    jax_cost.clear_compile_cache()
    gate = threading.Event()

    def slow(x):
        gate.wait(10.0)                 # holds the first worker in-flight
        return x + 1.0

    S = (jax.ShapeDtypeStruct((4,), np.float32),)
    k_slow = (1, 16, "requeue", "u", "slow", 4)
    k_fast = (1, 16, "requeue", "u", "fast", 4)
    fast = jax.jit(lambda x: x * 2.0)
    jax_cost.compile_ahead([(k_slow, jax.jit(slow), S), (k_fast, fast, S)])
    jax_cost.compile_ahead_quiesce()    # the first fleet ends
    threading.Timer(0.3, gate.set).start()
    jax_cost.compile_ahead([(k_fast, fast, S)], wait=True)
    out = jax_cost._aot_call(k_fast, fast, (np.ones(4, np.float32),))
    np.testing.assert_array_equal(np.asarray(out), 2.0)
    assert jax_cost.compile_ahead_counts() == (1, 0)
    assert jax_cost.compile_ahead_errors() == (0, None)
    jax_cost.clear_compile_cache()


def test_fleet_stats_record_compile_ahead_and_host_blocked():
    stats = {}
    _sweep(pipeline=True, stats=stats)
    assert stats["compile_ahead_hits"] >= 1
    assert stats["compile_ahead_misses"] >= 0
    assert stats["compile_ahead_errors"] == 0
    assert stats["compile_ahead_first_error"] is None
    assert stats["host_blocked_s"] >= 0.0
    assert stats["device_rounds_source"] == "explicit"


# ------------------------------------------------ counters under threads


def test_counters_thread_safe_under_hammer():
    jax_cost.reset_dispatch_count()
    n, threads = 20_000, []

    def hammer():
        for _ in range(n):
            jax_cost._count_dispatch()

    readers_ok = []

    def read():
        for _ in range(2_000):
            readers_ok.append(jax_cost.dispatch_count() >= 0)
            jax_cost.compilation_count()
            jax_cost.compile_ahead_counts()
            jax_cost.stack_prep_counts()
            jax_cost.host_blocked_s()

    for fn in (hammer, hammer, read):
        t = threading.Thread(target=fn)
        threads.append(t)
        t.start()
    for t in threads:
        t.join()
    assert jax_cost.dispatch_count() == 2 * n
    assert all(readers_ok)


# ------------------------------------------------ device_rounds chooser


def test_default_device_rounds_chooser():
    assert search.default_device_rounds("cpu") == 1
    assert search.default_device_rounds("gpu") == 4
    assert search.default_device_rounds("tpu") == 8
    assert search.default_device_rounds("metal") == 1   # unknown -> 1
    import jax
    assert search.default_device_rounds() == \
        search.default_device_rounds(jax.default_backend())


def test_device_rounds_resolution_and_provenance():
    import jax
    ms = search.MultiSearch([by_name("mm1")])
    assert ms.device_rounds == search.default_device_rounds()
    assert ms.device_rounds_source == f"default:{jax.default_backend()}"
    ms2 = search.MultiSearch([by_name("mm1")], device_rounds=2)
    assert ms2.device_rounds == 2
    assert ms2.device_rounds_source == "explicit"
    with pytest.raises(ValueError):
        search.MultiSearch([by_name("mm1")], device_rounds=0)
