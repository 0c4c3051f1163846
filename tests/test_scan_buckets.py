"""Scan dispatches pad their task axis to a bucket (``jax_cost.scan_slots``):

* every real task's segment output — per-generation kids and costs, the
  final population, the device carry and the restart state — is
  bit-identical to an unpadded dispatch of the same tasks, on the plain
  scan, the restart scan and the direct scan, fed from host populations
  and from device carries alike, and to the scan dispatched with its
  inputs and results as separate arrays;
* a served fleet whose admissions and completions move the task count
  builds no more scan programs than there are buckets, all of them
  ahead of their dispatch (``fleet.scan_builds``);
* a fleet run with compile-ahead builds every bucket of its groups
  ahead and none inline.
"""
import dataclasses
import threading
import time

import numpy as np
import pytest

from repro.configs.paper_workloads import by_name
from repro.core import jax_cost, search, trace
from repro.core.baselines import make_requests
from repro.core.es_ops import DeviceSegment, SegmentResult, \
    segment_shape_key
from repro.core.search import FleetConfig, MultiSearch, SearchTask
from repro.core.workload import spmm
from repro.launch.sweep_serve import SweepServer, submit

K = 2
KINDS = {
    "scan": ("sparsemap", {}),
    "restart": ("sparsemap", {"stagnation_restart": 1}),
    "direct": ("standard_es", {"pop_size": 24}),
}


@pytest.mark.parametrize("n,cap,want", [
    (1, None, 1), (2, None, 2), (3, None, 4), (5, None, 8), (9, None, 16),
    (13, None, 16), (13, 13, 13), (9, 13, 13), (5, 13, 8), (14, 13, 16)])
def test_scan_slots_are_powers_of_two_up_to_the_cap(n, cap, want):
    assert jax_cost.scan_slots(n, cap) == want


def _first_segments(kind, T, workloads=None):
    """The first DeviceSegment of ``T`` searches at different seeds, of
    the ``workloads`` in turn (mm1 by default), each driven through its
    host prologue."""
    method, kw = KINDS[kind]
    workloads = workloads or (by_name("mm1"),)
    models, segs = [], []
    for seed in range(T):
        spec, ev = search.get_evaluator(workloads[seed % len(workloads)],
                                        "cloud")
        gen, _ = make_requests(method, spec, search._platform("cloud"),
                               600, seed, device_rounds=K, **kw)
        req = next(gen)
        while not isinstance(req, DeviceSegment):
            req = gen.send(ev(req))
        models.append(ev)
        segs.append(req)
    return models, segs


def _assert_same(a, b):
    assert len(a.gens) == len(b.gens) == K
    for (ka, oa), (kb, ob) in zip(a.gens, b.gens):
        assert np.array_equal(ka, kb)
        assert oa.keys() == ob.keys()
        for name in oa:
            if isinstance(oa[name], dict):
                for f in oa[name]:
                    assert np.array_equal(oa[name][f], ob[name][f]), name
            else:
                assert np.array_equal(oa[name], ob[name]), name
    assert np.array_equal(a.final_pop, b.final_pop)
    assert np.array_equal(a.final_edp, b.final_edp)
    assert np.array_equal(np.asarray(a.carry), np.asarray(b.carry))
    assert a.state == b.state


@pytest.mark.parametrize("T", [1, 3, 5, 6])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_bucketed_scan_matches_the_unpadded_dispatch(kind, T):
    models, segs = _first_segments(kind, T)
    assert {s.kind for s in segs} == {"direct" if kind == "direct"
                                      else "es"}
    bucketed = jax_cost.run_segments(models, segs)
    unpadded = jax_cost.run_segments(models, segs, cap=T)
    for a, b in zip(bucketed, unpadded):
        _assert_same(a, b)
    # the next segment runs from the device carries of the first
    nxt = [dataclasses.replace(s, carry=r.carry, state=r.state)
           for s, r in zip(segs, bucketed)]
    for a, b in zip(jax_cost.run_segments(models, nxt),
                    jax_cost.run_segments(models, nxt, cap=T)):
        _assert_same(a, b)


def _per_array_reference(models, segs):
    """Each task's (gens, final_pop, final_edp, state) as the scan was
    dispatched before its inputs and results were packed: the kernel
    over the tasks, unpadded, fed each input as its own stacked array and
    the nine constants per task, every output fetched array by array and
    canonicalized generation by generation."""
    import jax
    import jax.numpy as jnp
    B, k, n_parents, n_elite, genes_per, kind, restart = \
        segment_shape_key(segs[0])
    d, n_pad, _, dens = models[0].signature
    topo = models[0].arch.topology
    ins = []
    for m, s in zip(models, segs):
        lay = jax_cost._padded_layout(m)
        dr = {kk: np.asarray(v) for kk, v in s.draws.items()}
        if kind == "direct":
            ins.append((np.asarray(s.pop, np.int32),
                        np.asarray(s.edp, np.float32), s.aux["scramble"],
                        s.aux["dim_sizes"], dr, m._np_consts))
            continue
        fm = np.zeros(lay.Lp, dtype=bool)
        fv = np.zeros(lay.Lp, dtype=np.int32)
        if s.fixed_genes:
            idx = lay.pad_index(np.asarray(list(s.fixed_genes)))
            fm[idx] = True
            fv[idx] = list(s.fixed_genes.values())
        dr["gene"] = lay.pad_index(dr["gene"]).astype(np.int32)
        dr["cuts"] = lay.pad_cut(dr["cuts"]).astype(np.int32)
        if restart:
            dr["fresh"] = lay.pad_rows(dr["fresh"].astype(np.int32))
            dr["best0"] = np.asarray([s.state[0]], dtype=np.float32)
            dr["since0"] = np.asarray([s.state[1]], dtype=np.int32)
        ins.append((lay.pad_rows(np.asarray(s.pop, np.int32)),
                    np.asarray(s.edp, np.float32),
                    lay.pad_vector(m.spec.gene_ub.astype(np.int32), 1),
                    fm, fv, dr, m._np_consts))
    if kind == "direct":
        vfn = jax_cost._direct_scan_task_fn(d, n_pad, topo, dens,
                                            n_parents, n_elite, genes_per)
    else:
        vfn = jax_cost._scan_task_fn(d, n_pad, topo, dens, n_parents,
                                     n_elite, genes_per, restart)
    args = jax.tree_util.tree_map(lambda *xs: jnp.asarray(np.stack(xs)),
                                  *ins)
    pop_f, edp_f, ys = jax.tree_util.tree_map(np.asarray,
                                              jax.jit(vfn)(*args))
    out = []
    for t, m in enumerate(models):
        lay = jax_cost._padded_layout(m)
        gens = []
        for g in range(k):
            kids = ys["canon" if kind == "direct" else "kids"][t, g]
            kout = jax_cost._canonical(dict(
                valid=ys["valid"][t, g], energy_pj=ys["energy_pj"][t, g],
                cycles=ys["cycles"][t, g]))
            if restart:
                kout["fresh"] = jax_cost._canonical(dict(
                    valid=ys["f_valid"][t, g],
                    energy_pj=ys["f_energy_pj"][t, g],
                    cycles=ys["f_cycles"][t, g]))
                kout["restarted"] = bool(ys["restarted"][t, g])
            gens.append((lay.unpad_rows(kids).astype(np.int64), kout))
        pop = pop_f[t] if kind == "direct" else lay.unpad_rows(pop_f[t])
        state = (float(ys["best"][t, 0]), int(ys["since"][t, 0])) \
            if restart else None
        out.append((gens, pop.astype(np.int64), edp_f[t], state))
    return out


UNIT = tuple(spmm(f"unit_{i}", 1, 1, 1, a, b)
             for i, (a, b) in enumerate(((0.5, 0.5), (0.3, 0.6), (0.6, 0.4))))


@pytest.mark.parametrize("T", [3, 5])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_packed_scan_matches_the_per_array_dispatch(kind, T):
    """The packed dispatch (one rows buffer up, constants from the
    per-task table, one result buffer back, filler slots on device
    carries) gives every task of T = 3 in 4 slots and T = 5 in 8, three
    workloads of one signature, what the per-array dispatch gives it
    unpadded, bit for bit: a first
    segment from host populations, then a second from the packed
    dispatch's device carries (the reference from its host copies)."""
    # direct genomes of unit dimensions always translate, so their rows
    # are priced with each task's own constants rather than all invalid
    wls = UNIT if kind == "direct" else tuple(
        by_name(w) for w in ("mm1", "mm2", "mm3"))
    models, segs = _first_segments(kind, T, wls)
    assert len({m.signature for m in models}) == 1
    assert jax_cost.scan_slots(T) == {3: 4, 5: 8}[T]
    for _ in range(2):
        got = jax_cost.run_segments(models, segs)
        want = _per_array_reference(
            models, [dataclasses.replace(s, carry=None) for s in segs])
        for r, (gens, pop, edp, state) in zip(got, want):
            _assert_same(r, SegmentResult(gens=gens, final_pop=pop,
                                          final_edp=edp, carry=r.carry,
                                          state=state))
        assert any(o["valid"].any() for r in got for _, o in r.gens)
        segs = [dataclasses.replace(s, carry=r.carry, state=r.state,
                                    pop=r.final_pop, edp=r.final_edp)
                for s, r in zip(segs, got)]


def _scan_events(t0):
    evs = trace.events(t0, time.perf_counter() + 1.0,
                       {"fleet.scan_tasks", "fleet.scan_builds"})
    tasks = [e for e in evs if e.name == "fleet.scan_tasks"]
    builds = [e for e in evs if e.name == "fleet.scan_builds"]
    return tasks, builds


def test_served_fleet_builds_one_scan_program_per_bucket():
    """Four closed-loop clients of a served fleet at device_rounds 8,
    each sending three queries of different budgets one after another:
    admissions and completions move the scan group's task count up and
    down, and the fleet builds each bucket's program once, ahead of the
    dispatch that needs it."""
    search.clear_cache()
    t0 = time.perf_counter()
    srv = SweepServer(port=0, config=FleetConfig(device_rounds=8))
    srv.start_background()
    ends = []

    def client(i):
        for j in range(3):
            t = SearchTask(spmm("q", 32, 16, 16, 0.5, 0.5), "cloud",
                           budget=4000 + 2000 * ((i + j) % 4),
                           seed=4 * j + i, name=f"q{i}.{j}")
            for ev in submit(srv.host, srv.port, t, timeout=300.0):
                if ev.get("event") in ("done", "failed"):
                    ends.append(ev["event"])

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(4)]
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300.0)
            assert not th.is_alive()
    finally:
        srv.stop()
    assert ends == ["done"] * 12
    tasks, builds = _scan_events(t0)
    counts = {int(e.value) for e in tasks}
    assert len(counts) >= 3, counts
    buckets = {jax_cost.scan_slots(n) for n in range(1, max(counts) + 1)}
    assert {e.attrs["slots"] for e in tasks} <= buckets
    assert len(builds) <= len(buckets)
    assert {e.attrs["source"] for e in builds} == {"ahead"}


@pytest.mark.parametrize("compile_ahead", [True, False])
def test_fleet_builds_every_bucket_ahead(compile_ahead):
    search.clear_cache()
    t0 = time.perf_counter()
    tasks = [SearchTask(by_name(w), "cloud", budget=700, seed=3)
             for w in ("mm1", "mm2", "mm3")]
    ms = MultiSearch(tasks, FleetConfig(device_rounds=4,
                                        compile_ahead=compile_ahead))
    ms.run()
    _, builds = _scan_events(t0)
    sources = [e.attrs["source"] for e in builds]
    if compile_ahead:
        # a run() fleet holds its three tasks: slots 1, 2 and 3
        assert sorted(e.attrs["slots"] for e in builds) == [1, 2, 3]
        assert sources == ["ahead"] * 3
        assert ms.stats["compile_ahead_misses"] == 0
    else:
        assert sources and set(sources) == {"inline"}
