"""CPU self-check of the benchmark, run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/

Covers the percentile and window arithmetic, discovery of every file by
name, the trace reducer on a small recorded chip trace, the reference
against the program's own float64 oracle, a whole run at a small size
with the look for a chip skipped, the control (the reference in
bfloat16 in the program's place) failing the limit, and each fault the
cells can have, planted under the timed path, turning `correct` false.
"""
from __future__ import annotations

import json
import math
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".bench_cache", "jax-test"))

import cell  # noqa: E402
import devtrace  # noqa: E402
import loadgen  # noqa: E402
import reference  # noqa: E402
import stats  # noqa: E402

CELLS = [w["name"] for w in
         json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


# ------------------------------------------------------------- arithmetic


@pytest.mark.parametrize("q,want", [(0.0, 1.0), (0.5, 2.5), (0.9, 3.7),
                                    (1.0, 4.0)])
def test_quantile_interpolates_like_numpy(q, want):
    import numpy as np
    vals = [4.0, 1.0, 3.0, 2.0]
    assert stats.quantile(vals, q) == pytest.approx(want)
    assert stats.quantile(vals, q) == pytest.approx(np.quantile(vals, q))


def test_quantile_of_nothing_is_none():
    assert stats.quantile([], 0.5) is None


def test_union_length_and_gaps():
    iv = [(0, 2), (1, 3), (5, 6), (5.5, 7), (9, 12)]
    assert stats.union_length(iv) == pytest.approx(3 + 2 + 3)
    assert stats.union_length(iv, 1, 10) == pytest.approx(2 + 2 + 1)
    assert stats.gaps(iv, 0, 10) == [(3, 5), (7, 9)]
    assert stats.gaps([], 0, 1) == [(0, 1)]


def test_progress_in_window_is_linear_between_points():
    pts = [(0.0, 0.0), (2.0, 100.0), (4.0, 300.0)]
    assert stats.progress_in_window(pts, 1.0, 3.0) == pytest.approx(150.0)
    assert stats.progress_in_window(pts, -5, 10) == pytest.approx(300.0)
    assert stats.progress_in_window(pts, 4.0, 9.0) == 0.0


def test_evals_in_window_and_in_a_part_of_it():
    class Q:
        t_submit = 0.0
        events = [{"event": "update", "evals": 100, "t_recv": 2.0},
                  {"event": "done", "evals": 300, "t_recv": 4.0}]
    ctx = dict(queries=[Q(), Q()], t_open=1.0, t_close=3.0)
    assert stats.evals_in_window(ctx) == pytest.approx(300.0)
    assert stats.evals_in_window(ctx, 2.0, 4.0) == pytest.approx(400.0)


# ------------------------------------------------------------- discovery


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_finds_its_files(name):
    c = cell.load_cell(name)
    assert c["config"]["workloads"] and c["config"]["accelerator"]
    tp = loadgen.traffic_params(c["traffic"])
    assert tp["clients_per_workload"] >= 1 and tp["methods"]
    names = [m["name"] for m in c["end_to_end"] + c["per_layer"]]
    assert "setup_s" in names
    for m in names:
        assert callable(cell.reader(m))


def test_every_reader_file_is_a_declared_metric():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    declared = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics"))
             if f.endswith(".py")}
    assert files == declared


def test_unknown_cell_is_an_error():
    with pytest.raises(cell.CellError):
        cell.load_cell("no_such.cell")


def test_reader_returning_none_leaves_metric_out(tmp_path):
    os.makedirs(tmp_path / "bench" / "metrics")
    (tmp_path / "bench" / "metrics" / "nothing.py").write_text(
        "def read(ctx):\n    return None\n")
    (tmp_path / "bench" / "metrics" / "one.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    got = cell.read_metrics([{"name": "nothing", "unit": "s"},
                             {"name": "one", "unit": "s"}], {},
                            root=str(tmp_path))
    assert got == {"one": {"value": 1.5, "unit": "s"}}


# ------------------------------------------------------------- traffic


def test_client_stream_is_seeded_and_balanced():
    me = ["a", "b", "c"]
    s1 = loadgen.ClientStream("w", me, 2 ** 33 + 7, 4)
    s2 = loadgen.ClientStream("w", me, 2 ** 33 + 7, 4)
    a = [s1.next() for _ in range(12)]
    assert a == [s2.next() for _ in range(12)]
    assert {q[0] for q in a} == {"w"}
    for k in range(4):
        assert sorted(q[1] for q in a[3 * k:3 * k + 3]) == me
    other = [loadgen.ClientStream("w", me, 2 ** 33 + 7, 5).next()[2],
             loadgen.ClientStream("w", me, 8, 4).next()[2]]
    assert a[0][2] not in other


def test_every_workload_has_its_clients():
    p = loadgen.traffic_params(dict(
        loop="closed", clients_per_workload=2, methods=["m"],
        drain_seconds=1, warmup_queries_per_client=1,
        warmup_quiet_seconds=1))
    st = loadgen.streams(["x", "y", "z"], p, 3)
    assert [c.workload for c in st] == ["x", "x", "y", "y", "z", "z"]
    assert [c.next()[2] for c in st] != \
        [c.next()[2] for c in loadgen.streams(["x", "y", "z"], p,
                                              loadgen.WARMUP_SEED)]


# ------------------------------------------------------------- trace


def test_reducer_on_recorded_trace():
    rec = json.load(open(os.path.join(BENCH, "testdata",
                                      "small_trace.json")))
    tr = rec["trace"]
    lo, hi = tr["window"]
    red = devtrace.reduce(tr, lo, hi)
    want = rec["reduced"]
    assert red["busy_s"] == pytest.approx(want["busy_s"])
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["device_ops"] == want["device_ops"]
    assert [g[0] for g in red["idle_gaps"]] == \
        [g[0] for g in want["idle_gaps"]]
    busy = sum(s for _, s in red["device_ops"])
    assert busy >= red["busy_s"] * (1 - 1e-9)


def test_reducer_finds_nothing_without_device_programs():
    assert devtrace.reduce({"devices": {}, "host": {}}, 0, 1) is None


# ------------------------------------------------------------- reference


@pytest.mark.parametrize("config", ["t3_spmm_cloud", "t3_conv_eyeriss"])
def test_reference_matches_program_oracle(config):
    import numpy as np
    from repro.configs.paper_workloads import by_name
    from repro.core.arch import as_arch
    from repro.core.baselines import fixed_mapping_genes_for_arch
    from repro.core.cost_model import evaluate
    from repro.core.encoding import GenomeSpec
    cfg = json.load(open(os.path.join(BENCH, "configs", f"{config}.json")))
    ref = reference.Reference(cfg["accelerator"])
    arch = as_arch(cfg["accelerator"]["name"])
    valid = 0
    for e in cfg["workloads"]:
        wl, sh = by_name(e["name"]), reference.Shape(e)
        assert sh.size == wl.dim_sizes
        spec = GenomeSpec(wl, arch=arch)
        rng = np.random.default_rng(3)
        base = np.zeros(spec.length, dtype=np.int64)
        for gi, v in fixed_mapping_genes_for_arch(spec, arch).items():
            base[gi] = v
        for g in spec.random_genomes(rng, 40):
            keep = np.ones(spec.length, bool)
            keep[rng.choice(spec.length, size=3, replace=False)] = False
            g[keep] = base[keep]
            want, got = evaluate(spec.decode(g), arch), ref.price(sh, g)
            assert want.valid == got["valid"]
            if want.valid:
                valid += 1
                assert math.log10(got["edp"]) == pytest.approx(
                    math.log10(want.edp), abs=1e-12)
    assert valid > 20


# ------------------------------------------------------------- whole runs

SMALL = dict(budget=800, workloads=3, seconds=4.0)


def small_cell(name="t3_conv_eyeriss.es"):
    """The cell at a size the CPU holds, on its own fleet settings."""
    c = cell.load_cell(name)
    c["config"]["budget"] = SMALL["budget"]
    c["config"]["workloads"] = c["config"]["workloads"][:SMALL["workloads"]]
    c["traffic"].update(warmup_queries_per_client=1,
                        warmup_quiet_seconds=0.5)
    return c


def cpu_device():
    import jax
    return dict(platform="cpu", kind="cpu", count=1, devices=jax.devices())


def run_small(name="t3_conv_eyeriss.es", seed=2 ** 31 + 5):
    import run
    return run.run_cell(small_cell(name), seed, SMALL["seconds"], False,
                        cpu_device())


def test_small_run_is_correct():
    res = run_small()
    assert res.pop("problems") == []
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"query_s_p50", "evals_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


def test_control_fails_the_limit_and_program_passes():
    import run
    from control import readings
    c = small_cell()
    sess = run.Session(c, cpu_device())
    try:
        ctx = sess.window(11, SMALL["seconds"], False)
    finally:
        sess.close()
    r = readings(c["config"], ctx["window_queries"])
    assert r["program"]["edp_gap_log10"] <= r["limit"]
    assert r["control"]["edp_gap_log10"] > r["limit"]
    assert r["control"]["designs_compared"] > 10


def _alter_answer(monkeypatch):
    """Every priced design's energy 0.2% off, where the kernel's output
    is made canonical."""
    from repro.core import jax_cost
    orig = jax_cost._canonical

    def altered(out):
        out = dict(out)
        out["energy_pj"] = out["energy_pj"] * out["energy_pj"].dtype.type(
            1.002)
        return orig(out)
    monkeypatch.setattr(jax_cost, "_canonical", altered)


def _half(out):
    """Only the second half of a batch is priced: each row of the first
    half takes the answer of the row half a batch after it (so a best
    taken by first index lands on a row whose answer is not its own)."""
    import numpy as np
    n = len(out["valid"])
    src = np.arange(n)
    src[:n - n // 2] += n // 2
    return {k: v[src] for k, v in out.items()}


def _half_batch_stacked(monkeypatch):
    """Only the second half of each mega-batch's rows is priced; the
    first half takes the answers of the second, which may belong to
    another query."""
    import numpy as np
    from repro.core import jax_cost
    orig = jax_cost.eval_stacked

    def half(models, batches, *a, **kw):
        kw["defer"] = False
        outs = orig(models, batches, *a, **kw)
        flat = _half({k: np.concatenate([o[k] for o in outs])
                      for k in outs[0]})
        cuts = np.cumsum([len(b) for b in batches])[:-1]
        return [dict(zip(flat, parts)) for parts in
                zip(*(np.split(v, cuts) for v in flat.values()))]
    monkeypatch.setattr(jax_cost, "eval_stacked", half)


def _stale_answers_stacked(monkeypatch):
    """The evaluation hands back the previous answers of the same size,
    as a step that leaves its state unchanged would."""
    from repro.core import jax_cost
    orig = jax_cost.eval_stacked
    last = {}

    def stale(models, batches, *a, **kw):
        kw["defer"] = False
        outs = orig(models, batches, *a, **kw)
        key = tuple(len(b) for b in batches)
        prev, last[key] = last.get(key), outs
        return prev if prev is not None else outs
    monkeypatch.setattr(jax_cost, "eval_stacked", stale)


def _short_budget(monkeypatch):
    """Searches count their evaluations against half their budget."""
    from repro.core import evolution
    orig = evolution._Budget.__init__

    def short(self, budget):
        orig(self, budget // 2)
    monkeypatch.setattr(evolution._Budget, "__init__", short)


@pytest.mark.parametrize("fault", [_alter_answer, _half_batch_stacked,
                                   _stale_answers_stacked, _short_budget])
def test_fault_under_the_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run_small(seed=77)
    assert not res["correct"], res["checks"]
