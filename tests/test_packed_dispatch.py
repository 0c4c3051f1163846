"""The transfer scheme of the stacked mega-batch (`jax_cost.eval_stacked`)
and of the segment scan (`jax_cost.run_segments`):

* one packed int32 row buffer and a device-resident per-task constants
  table give each task the rows a one-task call gives it, bit for bit,
  over mixed-size, mixed-workload, mixed-arch groups with padding, with
  tables of more slots than tasks, and rows the float64 oracle agrees
  with;
* a steady fleet round makes one rows upload and one result fetch, and
  uploads the table only when a task brings constants it lacks;
* compile-ahead builds every (row bucket, table slots) pair a fleet
  dispatches, so no stacked dispatch compiles inline;
* the table is reused by any group whose tasks it holds, grows into its
  free slots, and is rebuilt at the same slots when they are full;
* a scan dispatch makes one packed rows upload, one result fetch, and
  a carry upload only for a task's first segment (or a filler slot
  before the device holds its carry).
"""
import time

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
       "c": (spmm("pk_c", 64, 32, 32, 0.3, 0.6), "cloud"),
       "d": (spmm("pk_d", 32, 32, 64, 0.5, 0.5), "cloud"),
       "e": (spmm("pk_e", 64, 48, 32, 0.6, 0.4), "edge")}


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
    three tasks (4 slots) a one-task group still reads a 4-slot table,
    the same one, and both groups price as one-task calls do."""
    monkeypatch.setattr(jax_cost, "_STACK_TABLES", {})
    monkeypatch.setattr(jax_cost, "TABLE_FLOOR", 1)
    models, batches = _group(("a", "b", "c"), (20, 9, 40), seed=3)
    sig = models[0].signature
    _assert_matches_one_task_calls(models, batches,
                                   jax_cost.eval_stacked(models, batches))
    grown = jax_cost._STACK_TABLES[sig]
    assert grown.dev.shape[0] == 4
    one = jax_cost.eval_stacked(models[1:2], batches[1:2])
    _assert_matches_one_task_calls(models[1:2], batches[1:2], one)
    table = jax_cost._STACK_TABLES[sig]
    assert table is grown and len(table.row_of) == 3
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
    only on a step with a task whose workload it lacks: an admission of
    a new workload, never a retirement or an admission of a workload the
    table already holds."""
    monkeypatch.setattr(jax_cost, "_STACK_TABLES", {})
    tasks = [SearchTask(by_name("mm1"), "cloud", budget=1500, seed=0),
             SearchTask(by_name("mm3"), "cloud", budget=2500, seed=1)]
    ms = MultiSearch(tasks, FleetConfig(device_rounds=1,
                                        compile_ahead=False))
    ms.start()
    held_keys, step, uploads, quiet = set(), 0, 0, 0
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
        want_table = int(not held <= held_keys)
        held_keys |= held
        assert got == {("h2d", "rows"): 1, ("d2h", "out"): 1,
                       **({("h2d", "table"): 1} if want_table else {})}, \
            (step, got)
        assert step != 6 or not want_table
        uploads += want_table
        quiet += 1 - want_table
        step += 1
    ms.finish()
    # the first step and mm2's admission
    assert uploads == 2 and quiet > 4 * uploads


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


@pytest.mark.parametrize("case", ["subset", "new_content", "full"])
def test_table_is_reused_for_a_subset_and_grows_or_rebuilds(case,
                                                            monkeypatch):
    """The signature's table, grown to a, b, c in 4 slots: a group of
    tasks it holds reuses it (no copy); a group that brings new content
    adds its row in a free slot with one upload; a group whose new
    content finds no free slot rebuilds it from the group's rows alone,
    at the same 4 slots.  Every group prices as one-task calls do."""
    monkeypatch.setattr(jax_cost, "_STACK_TABLES", {})
    monkeypatch.setattr(jax_cost, "TABLE_FLOOR", 1)
    models, batches = _group(("a", "b", "c"), (20, 9, 40), seed=4)
    sig = models[0].signature
    jax_cost.eval_stacked(models, batches)
    grown = jax_cost._STACK_TABLES[sig]
    names = {"subset": ("c", "a"), "new_content": ("b", "d"),
             "full": ("e",)}[case]
    if case == "full":
        jax_cost.eval_stacked(*_group(("d",), (5,)))     # 4 of 4 slots
        grown = jax_cost._STACK_TABLES[sig]
        assert len(grown.row_of) == 4
    models, batches = _group(names, (17, 30)[:len(names)], seed=5)
    t0 = time.perf_counter()
    outs = jax_cost.eval_stacked(models, batches)
    got = _transfers(t0, time.perf_counter())
    table = jax_cost._STACK_TABLES[sig]
    key_of = {name: search.get_evaluator(*WLS[name])[1]._table_key
              for name in WLS}
    assert table.dev.shape[0] == 4
    if case == "subset":
        assert table is grown and ("h2d", "table") not in got
    else:
        assert got[("h2d", "table")] == 1
        want = {"new_content": "abcd", "full": "e"}[case]
        assert table.row_of.keys() == {key_of[n] for n in want}
        # rows the table kept stay where they were
        for k, i in table.row_of.items():
            if k in grown.row_of and case == "new_content":
                assert grown.row_of[k] == i
    _assert_matches_one_task_calls(models, batches, outs)


def _scan_transfers(t0, t1):
    got = {}
    for e in trace.events(t0, t1, {jax_cost.SCAN_TRANSFERS}):
        key = (e.attrs["dir"], e.attrs["what"])
        got[key] = got.get(key, 0) + e.value
    return got


def test_k8_fleet_makes_two_scan_copies_per_dispatch(monkeypatch):
    """A fleet at device_rounds 8 whose scan group grows and shrinks
    (an admission mid-run, tasks of different budgets): each scan
    dispatch sends one packed rows buffer and a carry for each task on
    its first segment; filler slots take carries from the host only
    until the device holds enough; every dispatch's results come back in
    one copy.  The stacked path's own copies are unchanged: one rows
    buffer and one result per mega-batch, the table only when new
    content arrives, and no scan copy counted among them."""
    monkeypatch.setattr(jax_cost, "_STACK_TABLES", {})
    monkeypatch.setattr(jax_cost, "_FILLERS", {})
    scans = []
    real = jax_cost.run_segments

    def logged(models, segs, **kw):
        t0 = time.perf_counter()
        out = real(models, segs, **kw)
        scans.append((t0, time.perf_counter(), len(models),
                      jax_cost.scan_slots(len(models), kw.get("cap")),
                      sum(s.carry is None for s in segs)))
        return out

    monkeypatch.setattr(jax_cost, "run_segments", logged)
    tasks = [SearchTask(by_name(w), "cloud", budget=b, seed=i)
             for i, (w, b) in enumerate((("mm1", 3000), ("mm3", 5000),
                                         ("mm2", 4000)))]
    ms = MultiSearch(tasks, FleetConfig(device_rounds=8,
                                        compile_ahead=False))
    t_start = time.perf_counter()
    ms.start()
    step = 0
    while not ms.done:
        if step == 4:
            ms.admit(SearchTask(by_name("mm1"), "cloud", budget=3000,
                                seed=7))
        ms.step()
        step += 1
    ms.finish()
    t_end = time.perf_counter()
    assert len(scans) >= 4 and len({T for _, _, T, _, _ in scans}) >= 2
    assert any(slots > T for _, _, T, slots, _ in scans)
    fillers = 0
    for t0, t1, T, slots, first in scans:
        got = _scan_transfers(t0, t1)
        assert got.pop(("h2d", "rows")) == 1
        extra = got.pop(("h2d", "carry"), 0) - first
        assert 0 <= extra <= slots - T
        fillers += extra
        assert not got or set(got) == {("d2h", "out")}
    total = _scan_transfers(t_start, t_end)
    assert total[("d2h", "out")] == len(scans)
    assert fillers <= max(slots - T for _, _, T, slots, _ in scans)
    firsts = sum(first for *_, first in scans)
    assert (sum(total.values()) - firsts - fillers) / len(scans) == 2
    stacked = trace.events(t_start, t_end, {"fleet.dispatch"})
    n_stacked = sum(1 for e in stacked if e.attrs.get("kind") == "stacked")
    plain = _transfers(t_start, t_end)
    assert plain[("h2d", "rows")] == plain[("d2h", "out")] == n_stacked
    assert set(plain) <= {("h2d", "rows"), ("d2h", "out"),
                          ("h2d", "table")}
