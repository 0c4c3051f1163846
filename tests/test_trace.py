"""The trace recorder (``repro.core.trace``) and what reads it:

* recorder semantics: window selection, a ring that has dropped the
  window's start reads None, counter totals, threads recording at once,
* the benchmark's per-layer readers over a small in-process sweep server
  (two concurrent queries), whose worker-thread shares add up to one,
* the server's ``stats`` op keeps cumulative ``trace`` totals across
  epochs,
* the spans land in a ``jax.profiler`` trace's host plane,
* the kernel programs' XLA module names are the ones the benchmark's
  ``kernel_us_per_eval`` matches,
* lint rule R4 guards the recorder's state.
"""
import ast
import importlib.util
import math
import os
import sys
import threading
import time
from collections import deque

import pytest

from repro.core import jax_cost, search, trace
from repro.core.search import FleetConfig, MultiSearch, SearchTask
from repro.core.workload import spmm
from repro.launch.sweep_serve import SweepServer, request, submit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

#: the benchmark's readers of the recorder, and the worker's leaf spans
READERS = ("queue_wait_s_p50", "prologue_s_p50", "epoch_start_share",
           "emit_share", "advance_share", "dispatch_share",
           "pad_row_share", "worker_untraced_share")
LEAVES = ("serve.wait", "fleet.start", "serve.admit", "fleet.dispatch",
          "fleet.block", "fleet.advance", "serve.emit")


def _bench_module(name):
    """A module of bench/ loaded by path (its siblings importable)."""
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}", os.path.join(BENCH, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _task(name, m, seed, budget=600):
    return SearchTask(spmm(name, m, 16, 8, 0.5, 0.5), "cloud",
                      budget=budget, seed=seed, method="sparsemap")


# ------------------------------------------------------------- recorder


def test_events_select_the_window():
    base = time.perf_counter() - 100.0      # before this test's events
    trace.record("t.win", base + 0.0, base + 1.0, k="before")
    trace.record("t.win", base + 1.5, base + 2.5, k="across")
    trace.record("t.win", base + 3.0, base + 4.0, k="inside")
    trace.record("t.win", base + 5.0, base + 5.0, k="at_close")
    trace.record("t.win", base + 2.0, base + 2.0, k="at_open")
    got = trace.events(base + 2.0, base + 5.0, {"t.win"})
    assert [e.attrs["k"] for e in got] == ["across", "inside", "at_open"]
    assert all(e.thread == threading.current_thread().name for e in got)
    assert trace.events(base + 2.0, base + 5.0, {"t.other"}) == []


def test_dropped_window_start_reads_none(monkeypatch):
    monkeypatch.setattr(trace, "CAPACITY", 4)
    monkeypatch.setattr(trace, "_RING", deque(maxlen=4))
    monkeypatch.setattr(trace, "_DROPPED_T1", -math.inf)
    for i in range(6):
        trace.record("t.ring", float(i), i + 0.5)
    # the events ending at 0.5 and 1.5 were dropped
    assert trace.events(1.0, 10.0) is None
    assert trace.events(1.5, 10.0) is None
    held = trace.events(1.6, 10.0)
    assert [e.t0 for e in held] == [2.0, 3.0, 4.0, 5.0]


def test_counter_and_interval_totals():
    before = trace.totals().get("t.count", {"count": 0, "value": 0})
    trace.count("t.count", 3, sig="a")
    trace.count("t.count", 4)
    after = trace.totals()["t.count"]
    assert after == {"count": before["count"] + 2,
                     "value": before["value"] + 7}
    assert trace.total("t.count") == before["value"] + 7
    trace.record("t.secs", 10.0, 10.25)
    assert trace.totals()["t.secs"]["seconds"] >= 0.25
    assert trace.total("t.never") == 0
    # a count is an instant carrying its increment
    t0 = time.perf_counter()
    trace.count("t.count", 5)
    ev = trace.events(t0, time.perf_counter() + 1, {"t.count"})[-1]
    assert ev.value == 5 and ev.t0 == ev.t1


def test_threads_recording_at_once():
    n = 5_000
    c0 = trace.totals().get("t.ham", {"value": 0})["value"]
    s0 = trace.totals().get("t.hspan", {"count": 0})["count"]
    t_lo = time.perf_counter()

    def hammer():
        for _ in range(n):
            trace.count("t.ham")
            with trace.span("t.hspan"):
                pass

    threads = [threading.Thread(target=hammer, name=f"ham-{i}")
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tot = trace.totals()
    assert tot["t.ham"]["value"] == c0 + 2 * n
    assert tot["t.hspan"]["count"] == s0 + 2 * n
    evs = trace.events(t_lo, time.perf_counter(), {"t.hspan"})
    assert len(evs) == 2 * n
    assert {e.thread for e in evs} == {"ham-0", "ham-1"}


def test_jax_cost_getters_read_the_recorder():
    jax_cost.reset_dispatch_count()
    jax_cost.reset_host_blocked_s()
    d0 = trace.total(jax_cost.DISPATCHES)
    for _ in range(3):
        jax_cost._count_dispatch()
    assert jax_cost.dispatch_count() == 3
    assert trace.total(jax_cost.DISPATCHES) == d0 + 3
    jax_cost._time_block(lambda: time.sleep(0.01))
    assert jax_cost.host_blocked_s() >= 0.01
    jax_cost.reset_dispatch_count()
    assert jax_cost.dispatch_count() == 0


# ------------------------------------------------------- served readers


@pytest.fixture(scope="module")
def served():
    """A small sweep server: two concurrent queries inside a window,
    then one more query in an epoch of its own, with a ``stats`` reading
    after each epoch."""
    srv = SweepServer(port=0, config=FleetConfig(device_rounds=1),
                      warm_start=False)
    srv.start_background()
    try:
        def stats():
            return next(request(srv.host, srv.port,
                                {"op": "stats"}))["stats"]

        def send(i):
            for _ in submit(srv.host, srv.port, _task(f"w{i}", 16 + 8 * i,
                                                      i)):
                pass

        t_open = time.perf_counter()
        threads = [threading.Thread(target=send, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        t_close = time.perf_counter()
        first = stats()
        send(2)
        second = stats()
    finally:
        srv.stop()
    ctx = dict(t_open=t_open, t_close=t_close, window_s=t_close - t_open)
    return ctx, first, second


def test_readers_read_the_served_window(served):
    ctx, _, _ = served
    cell = _bench_module("cell")
    got = {m: cell.reader(m, ROOT)(ctx) for m in READERS}
    for m, v in got.items():
        assert v is not None, m
        if m.endswith("_s_p50"):
            assert v > 0, (m, v)
        else:
            assert 0.0 <= v <= 1.0, (m, v)
    assert got["prologue_s_p50"] < ctx["window_s"]
    assert got["pad_row_share"] > 0          # small batches pad to 64
    # the worker's leaf spans with the untraced rest cover the window
    union_length = _bench_module("stats").union_length
    lo, hi = ctx["t_open"], ctx["t_close"]
    evs = trace.events(lo, hi, set(LEAVES))
    leaf = {n: union_length([(e.t0, e.t1) for e in evs if e.name == n],
                            lo, hi) / ctx["window_s"]
            for n in LEAVES if n != "fleet.advance"}
    leaf["fleet.advance"] = got["advance_share"]
    assert leaf["fleet.start"] == pytest.approx(got["epoch_start_share"])
    assert leaf["serve.emit"] == pytest.approx(got["emit_share"])
    assert leaf["fleet.dispatch"] == pytest.approx(got["dispatch_share"])
    assert sum(leaf.values()) + got["worker_untraced_share"] == \
        pytest.approx(1.0, abs=1e-6)


def test_transfers_per_batch_reads_the_served_window(served):
    """Each stacked mega-batch of the served window sends one rows
    buffer and fetches one result; constants tables go up only as the
    fleet's set of queries changes, so the ratio stays near 2."""
    ctx, _, _ = served
    got = _bench_module("cell").reader("transfers_per_batch", ROOT)(ctx)
    evs = trace.events(ctx["t_open"], ctx["t_close"],
                       {jax_cost.TRANSFERS})
    whats = {(e.attrs["dir"], e.attrs["what"]) for e in evs}
    assert whats == {("h2d", "rows"), ("h2d", "table"), ("d2h", "out")}
    assert 2.0 < got < 2.5, got


def test_stats_trace_totals_survive_epochs(served):
    _, first, second = served
    assert second["epochs"] == first["epochs"] + 1
    a, b = first["trace"], second["trace"]
    assert b["fleet.start"]["count"] == a["fleet.start"]["count"] + 1
    assert b["fleet.dispatches"]["value"] > a["fleet.dispatches"]["value"]
    assert b["serve.queue"]["count"] == a["serve.queue"]["count"] + 1
    # the fleet's own counters restart with the epoch
    assert second["fleet"]["dispatches"] < b["fleet.dispatches"]["value"]


def test_readers_find_nothing_without_the_recorder(monkeypatch):
    import repro.core
    cell = _bench_module("cell")
    # as in a program that has no recorder module
    monkeypatch.delattr(repro.core, "trace")
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    ctx = dict(t_open=0.0, t_close=1.0, window_s=1.0)
    for m in READERS + ("transfers_per_batch",):
        assert cell.reader(m, ROOT)(ctx) is None, m


# ------------------------------------------------------- profiler trace


def test_spans_land_in_the_profiler_host_plane(tmp_path):
    """A fleet stepped on a thread named as the server names its worker
    keeps its spans on a host line of its own, though another Python
    thread records annotations too (Python threads otherwise share one
    line name, and the trace reader keys lines by name)."""
    import jax
    devtrace = _bench_module("devtrace")
    ms = MultiSearch([_task("pt", 16, 3, budget=400)],
                     FleetConfig(device_rounds=1, compile_ahead=False))
    ms.start()
    ms.step()                   # compiles outside the trace

    stepped, traced = threading.Event(), threading.Event()

    def worker():
        # alive until the trace is collected, as the server's worker is:
        # the trace names a thread's line when it collects
        trace.name_os_thread("fleet-test")
        for _ in range(2):
            ms.step()
        stepped.set()
        traced.wait(60)

    def other():
        with trace.span("t.other"):
            stepped.wait(60)
        traced.wait(60)

    threads = [threading.Thread(target=fn) for fn in (worker, other)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        for t in threads:
            t.start()
        stepped.wait(60)
    finally:
        jax.profiler.stop_trace()
        traced.set()
        for t in threads:
            t.join()
    tr = devtrace.load(devtrace.latest_xplane(str(tmp_path)))
    lines = [ln for ln in tr["host"] if ln.startswith("fleet-test")]
    assert len(lines) == 1, sorted(tr["host"])
    names = {name for name, _, _ in tr["host"][lines[0]]}
    assert {"fleet.dispatch", "fleet.block", "fleet.advance"} <= names
    assert "t.other" not in names


# ------------------------------------------------------- kernel names


def _module_name(job):
    _, fn, structs = job
    head = fn.lower(*structs).as_text().split("\n", 1)[0]
    return head.split("@", 1)[1].split()[0]


def test_kernel_module_names_match_the_benchmark():
    from repro.configs.paper_workloads import by_name
    from repro.core.direct_encoding import DirectValueSpec
    path = os.path.join(BENCH, "metrics", "kernel_us_per_eval.py")
    tree = ast.parse(open(path).read())
    want = next(ast.literal_eval(n.value) for n in tree.body
                if isinstance(n, ast.Assign) and
                n.targets[0].id == "KERNEL_MODULES")
    spec, ev = search.get_evaluator(by_name("mm3"), "cloud")
    dspec = DirectValueSpec(spec)
    got = {
        "stacked": _module_name(jax_cost.stacked_compile_job(ev, 64)),
        "scan": _module_name(jax_cost.scan_compile_job(ev, 20, 2, 8, 2,
                                                       2, 2)),
        "dscan": _module_name(jax_cost.direct_scan_compile_job(
            ev, 20, 2, 8, 2, 2, 2, dspec.length, dspec.n_perm_codes)),
    }
    assert got["stacked"] == f"jit_{jax_cost.EVAL_PROGRAM}"
    assert got["scan"] == got["dscan"] == f"jit_{jax_cost.SCAN_PROGRAM}"
    assert set(got.values()) == set(want)


# ------------------------------------------------------- lint rule R4


@pytest.mark.parametrize("path,src,n", [
    ("src/repro/core/trace.py",
     "def f(ev):\n    global _DROPPED_T1\n    _RING.append(ev)\n"
     "    _TOTALS['x'] = 1\n    _DROPPED_T1 = 2.0\n", 3),
    ("src/repro/core/trace.py",
     "def f(ev):\n    with _LOCK:\n        _RING.append(ev)\n", 0),
    # the counters left jax_cost: their names are no longer its state
    ("src/repro/core/jax_cost.py",
     "def f():\n    global _DISPATCHES\n    _DISPATCHES += 1\n", 0),
    ("src/repro/core/jax_cost.py",
     "def f(k):\n    _RESET_AT[k] = 0\n", 1),
])
def test_r4_guards_the_recorder_state(path, src, n):
    from repro.analysis.lint import lint_source
    from repro.analysis.rules.r4_counter_lock import CounterLockRule
    vs = lint_source(src, path, rules=[CounterLockRule()])
    assert len(vs) == n, [str(v) for v in vs]
