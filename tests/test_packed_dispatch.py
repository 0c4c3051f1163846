"""The stacked mega-batch's transfer scheme (`jax_cost.eval_stacked`):

* one packed int32 row buffer and a device-resident per-task constants
  table give each task the rows a one-task call gives it, bit for bit,
  over mixed-size, mixed-workload, mixed-arch groups with padding, with
  tables of more slots than tasks, and rows the float64 oracle agrees
  with;
* a steady fleet round makes one rows upload and one result fetch, and
  uploads the table only when the group's set of tasks changes;
* compile-ahead builds every (row bucket, table slots) pair a fleet
  dispatches, so no stacked dispatch compiles inline.
"""
import numpy as np
import pytest

from repro.configs.paper_workloads import by_name
from repro.core import jax_cost, search, trace
from repro.core.cost_model import check_against_oracle
from repro.core.search import FleetConfig, MultiSearch, SearchTask
from repro.core.workload import spmm

# one (3, 16, cloud topology, uniform) signature across both archs
WLS = {"a": (spmm("pk_a", 32, 64, 48, 0.2, 0.5), "cloud"),
       "b": (spmm("pk_b", 48, 32, 64, 0.4, 0.3), "edge"),
       "c": (spmm("pk_c", 64, 32, 32, 0.3, 0.6), "cloud")}


def _group(names, sizes, seed=0):
    rng = np.random.default_rng(seed)
    models, batches = [], []
    for name, n in zip(names, sizes):
        spec, ev = search.get_evaluator(*WLS[name])
        models.append(ev)
        batches.append(spec.random_genomes(rng, n))
    assert len({m.signature for m in models}) == 1
    return models, batches


def _assert_matches_one_task_calls(models, batches, outs):
    """Each task's rows equal its own one-task call's, bit for bit, and
    the float64 oracle agrees with them under its shared rule — so a
    packing or gather fault that hit both sides alike still fails."""
    for m, g, out in zip(models, batches, outs):
        want = m(g)
        assert list(out) == list(want)
        for k in want:
            np.testing.assert_array_equal(out[k], want[k])
        agr = check_against_oracle(m.spec, m.arch, g, out)
        assert agr.checked == len(g)
        assert not agr.disagreements, agr.disagreements[:5]


@pytest.mark.parametrize("names,sizes,pad_floor", [
    (("a", "b", "a", "c"), (37, 50, 5, 64), 0),       # 156 rows -> 256
    (("a", "b", "a", "c"), (37, 50, 5, 64), 1024),    # watermark floor
    (("c",), (3,), 0),                                # one task -> 64
    (("a", "b"), (37, 50), 512),          # cloud + edge, sticky shape
], ids=["mixed", "floor", "single", "two_archs_floor"])
def test_packed_rows_match_one_task_calls(names, sizes, pad_floor,
                                          monkeypatch):
    monkeypatch.setattr(jax_cost, "_STACK_TABLES", {})
    models, batches = _group(names, sizes)
    outs = jax_cost.eval_stacked(models, batches, pad_floor=pad_floor)
    table = jax_cost._STACK_TABLES[models[0].signature]
    # a repeated workload shares its table row
    assert len(table.row_of) == len(set(names))
    assert table.dev.shape[0] == jax_cost.TABLE_FLOOR > len(set(names))
    _assert_matches_one_task_calls(models, batches, outs)


def test_grown_table_keeps_its_slots_and_results(monkeypatch):
    """Slots are a power of two that never shrinks per signature: after
    three tasks (4 slots) a one-task group still gets a 4-slot table,
    and both groups price as one-task calls do."""
    monkeypatch.setattr(jax_cost, "_STACK_TABLES", {})
    monkeypatch.setattr(jax_cost, "TABLE_FLOOR", 1)
    models, batches = _group(("a", "b", "c"), (20, 9, 40), seed=3)
    sig = models[0].signature
    _assert_matches_one_task_calls(models, batches,
                                   jax_cost.eval_stacked(models, batches))
    assert jax_cost._STACK_TABLES[sig].dev.shape[0] == 4
    one = jax_cost.eval_stacked(models[1:2], batches[1:2])
    _assert_matches_one_task_calls(models[1:2], batches[1:2], one)
    table = jax_cost._STACK_TABLES[sig]
    assert table.dev.shape[0] == 4 and len(table.row_of) == 1
    assert jax_cost.table_slots(sig, 1) == 4


def _transfers(t0, t1):
    got = {}
    for e in trace.events(t0, t1, {jax_cost.TRANSFERS}):
        key = (e.attrs["dir"], e.attrs["what"])
        got[key] = got.get(key, 0) + e.value
    return got


def test_fleet_round_makes_one_upload_and_one_fetch(monkeypatch):
    """Each step of a one-signature fleet sends one rows buffer and
    fetches one result; the table goes up on the first step and then
    only on steps whose set of tasks differs from the last step's: an
    admission of a new workload, a retirement, never an admission of a
    workload the group already holds."""
    import time
    monkeypatch.setattr(jax_cost, "_STACK_TABLES", {})
    tasks = [SearchTask(by_name("mm1"), "cloud", budget=1500, seed=0),
             SearchTask(by_name("mm3"), "cloud", budget=2500, seed=1)]
    ms = MultiSearch(tasks, FleetConfig(device_rounds=1,
                                        compile_ahead=False))
    ms.start()
    prev, step, uploads, quiet = None, 0, 0, 0
    while not ms.done:
        if step == 3:
            ms.admit(SearchTask(by_name("mm2"), "cloud", budget=1200,
                                seed=2))
        if step == 6:       # the group already holds mm1: no upload
            ms.admit(SearchTask(by_name("mm1"), "cloud", budget=1000,
                                seed=5))
        assert len({st.signature for st in ms._alive}) == 1
        held = frozenset(st.ev._table_key for st in ms._alive)
        t0 = time.perf_counter()
        ms.step()
        got = _transfers(t0, time.perf_counter())
        want_table = int(held != prev)
        assert got == {("h2d", "rows"): 1, ("d2h", "out"): 1,
                       **({("h2d", "table"): 1} if want_table else {})}, \
            (step, got)
        assert step != 6 or not want_table
        uploads += want_table
        quiet += 1 - want_table
        prev, step = held, step + 1
    ms.finish()
    # the first step, mm2's admission and the retirements of the last
    # mm2 and the last mm1
    assert uploads >= 4 and quiet > 4 * uploads


def test_compile_ahead_covers_every_stacked_dispatch(monkeypatch):
    """Every (row bucket, table slots) pair a fleet's mega-batches
    dispatch was built by compile-ahead from ``stacked_compile_job``'s
    structures: each such dispatch finds its AOT executable."""
    jax_cost.clear_compile_cache()
    search.clear_cache()
    seen = []
    real = jax_cost._aot_call

    def logged(key, fn, args):
        if key[4] == "stacked":
            seen.append((key, jax_cost._aot_lookup(key) is not None))
        return real(key, fn, args)

    monkeypatch.setattr(jax_cost, "_aot_call", logged)
    tasks = [SearchTask(by_name(w), "cloud", budget=700, seed=3)
             for w in ("mm1", "mm2", "mm3")]
    ms = MultiSearch(tasks, FleetConfig(device_rounds=1))
    ms.run()
    assert seen and all(hit for _, hit in seen), \
        sorted({k for k, hit in seen if not hit})
    assert {k[6] for k, _ in seen} == {jax_cost.TABLE_FLOOR}
    assert len({k[5] for k, _ in seen}) >= 2
    assert ms.stats["compile_ahead_misses"] == 0
