"""The sweep server tentpole, from the engine up:

* mid-run admission: a query admitted into a RUNNING fleet joins its
  signature group's mega-batch — 1.0 dispatches/round and NO extra XLA
  compilations vs the single-client fleet,
* checkpointed populations: save the in-flight fleet at round r, kill
  it, restore — the resumed run's final best-EDP / history is
  BIT-IDENTICAL to the uninterrupted run at fixed seeds,
* server crash recovery via the supervisor (injected step failure),
* warm-start library hit/miss semantics (and the methods that refuse
  runtime kwargs),
* slow tier: the full subprocess smoke — server CLI + two concurrent
  same-signature clients + one different-topology client, coalescing
  asserted via the stats op, clean shutdown with exit code 0.
"""
import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt_lib
from repro.core import jax_cost
from repro.core.search import FleetConfig, MultiSearch, SearchTask
from repro.core.workload import spmm
from repro.launch import sweep_serve
from repro.launch.sweep_serve import (GenomeLibrary, SweepServer,
                                      library_key, pack_fleet,
                                      restore_fleet, submit)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGET = 800
CFG = FleetConfig(device_rounds=1)


def task(name="wa", m=16, seed=5, budget=BUDGET, method="sparsemap",
         platform="cloud"):
    return SearchTask(spmm(name, m, 16, 8, 0.5, 0.5), platform,
                      budget=budget, seed=seed, method=method)


# ------------------------------------------------- mid-run admission


def test_admission_coalesces_into_shared_mega_batch():
    """Admit a same-signature task mid-run: from then on the fleet must
    keep issuing ONE device dispatch per round (the shared mega-batch),
    and the whole run must compile NO MORE XLA programs than a fleet
    that started with both tasks (admission itself is compile-free; the
    only new shape is the bigger mega-batch, which the from-start fleet
    pays for too)."""
    cfg = FleetConfig(device_rounds=1, compile_ahead=False)   # deterministic counts

    jax_cost.clear_compile_cache()
    MultiSearch([task("a1", seed=5), task("a2", seed=6)], cfg).run()
    compiles_from_start = jax_cost.compilation_count()

    jax_cost.clear_compile_cache()
    ms = MultiSearch([task("a1", seed=5)], cfg)
    ms.start()
    for _ in range(3):
        ms.step()
    d0 = ms.stats_snapshot()["dispatches"]
    r0 = ms.stats_snapshot()["rounds"]
    name = ms.admit(task("a2", seed=6))
    assert name == "a2@cloud"
    while ms.step():
        pass
    results = ms.finish()
    st = ms.stats
    # every post-admission round is one shared dispatch
    assert (st["dispatches"] - d0) == (st["rounds"] - r0), st
    assert jax_cost.compilation_count() <= compiles_from_start
    assert len(st["signatures"]) == 1
    assert results["a2@cloud"].best_edp == \
        MultiSearch([task("a2", seed=6)], cfg).run()["a2@cloud"].best_edp


def test_admitted_task_result_matches_solo_run():
    """Coalescing must not perturb trajectories: a task admitted at
    round 3 finishes bit-identical to the same task run alone."""
    solo = MultiSearch([task("adm", seed=9)], CFG).run()["adm@cloud"]
    ms = MultiSearch([task("host_t", seed=5)], CFG)
    ms.start()
    for _ in range(3):
        ms.step()
    ms.admit(task("adm", seed=9))
    while ms.step():
        pass
    joined = ms.finish()["adm@cloud"]
    assert joined.best_edp == solo.best_edp
    assert np.array_equal(joined.history, solo.history)


def test_pop_done_and_result_of():
    ms = MultiSearch([task("pd1", budget=300)], CFG)
    ms.start()
    while ms.step():
        pass
    done = dict(ms.pop_done())
    assert "pd1@cloud" in done
    assert ms.pop_done() == []          # drained
    assert ms.result_of("pd1@cloud").best_edp == \
        done["pd1@cloud"].best_edp
    with pytest.raises(KeyError):
        ms.result_of("nope")


# --------------------------------------- checkpoint / crash recovery


def _run_tasks():
    out = []
    for nm, m in (("ck_a", 16), ("ck_b", 24)):
        t = task(nm, m=m, seed=5)
        t.runtime_kw["state_out"] = {}
        out.append(t)
    return out


def test_checkpoint_round_trip_is_bit_identical():
    """Save the in-flight fleet at round r, kill it, restore from disk:
    the resumed run's final results equal the uninterrupted run's
    bit-for-bit (best EDP, genome, full history, eval counts)."""
    ref = MultiSearch(_run_tasks(), CFG).run()

    ms = MultiSearch(_run_tasks(), CFG)
    ms.start()
    for _ in range(6):
        ms.step()
    arrays, meta = pack_fleet(ms)
    with tempfile.TemporaryDirectory() as d:
        ckpt_lib.save_flat(d, int(ms._rounds), arrays, extra_meta=meta)
        del ms                          # the "kill"
        arrays2, meta2 = ckpt_lib.load_flat(d, ckpt_lib.latest_step(d))
    res = restore_fleet(arrays2, meta2).run()

    for name in ref:
        a, b = ref[name], res[name]
        assert b.best_edp == a.best_edp, name
        assert np.array_equal(b.best_genome, a.best_genome), name
        assert np.array_equal(b.history, a.history), name
        assert (b.evals, b.valid_evals) == (a.evals, a.valid_evals)


def test_server_recovers_from_worker_crash(monkeypatch):
    """Kill the fleet mid-sweep (injected exception on step call 6,
    after the round-4 checkpoint): the supervisor restores from the
    latest checkpoint and the client still receives the bit-identical
    final best-EDP."""
    ref = MultiSearch([task("cr", seed=5)], CFG).run()["cr@cloud"]

    calls = {"n": 0}
    orig_step = MultiSearch.step

    def crashy(self):
        calls["n"] += 1
        if calls["n"] == 6:
            raise RuntimeError("injected worker crash")
        return orig_step(self)

    monkeypatch.setattr(MultiSearch, "step", crashy)
    with tempfile.TemporaryDirectory() as d:
        srv = SweepServer(port=0, config=CFG, ckpt_dir=d, ckpt_every=4)
        srv.start_background()
        try:
            evs = list(submit(srv.host, srv.port, task("cr", seed=5)))
            done = [e for e in evs if e.get("event") == "done"]
            st = next(iter(sweep_serve.request(
                srv.host, srv.port, {"op": "stats"})))["stats"]
            assert st["restarts"] == 1
            assert done[0]["best_edp"] == ref.best_edp
            assert done[0]["evals"] == ref.evals
            assert done[0]["best_genome"] == \
                np.asarray(ref.best_genome).tolist()
            # clean completion wipes the spent checkpoints
            assert not any(x.startswith("step_") for x in os.listdir(d))
        finally:
            srv.stop()


def test_checkpointing_requires_device_rounds_one():
    with pytest.raises(ValueError, match="device_rounds"):
        SweepServer(port=0, config=FleetConfig(device_rounds=4),
                    ckpt_dir="/tmp/nope")


# ------------------------------------------------- warm-start library


def test_library_hit_miss_and_keying():
    lib = GenomeLibrary()
    ta, tb = task("lw", seed=1), task("lw", seed=2)
    assert library_key(ta) == library_key(tb)       # content, not seed
    assert library_key(task("lw", m=24)) != library_key(ta)
    assert lib.lookup(ta) is None and lib.misses == 1

    res = MultiSearch([task("lw", seed=1)], CFG).run()["lw@cloud"]
    assert np.isfinite(res.best_edp)        # budget finds a valid genome
    lib.record(ta, res)
    rows = lib.lookup(tb)
    assert lib.hits == 1
    assert rows.shape == (1, len(res.best_genome))
    assert np.array_equal(rows[0], res.best_genome)
    # worse result does not displace the stored best
    worse = type(res)(best_edp=res.best_edp * 10,
                      best_genome=np.zeros_like(res.best_genome),
                      history=res.history, evals=1, valid_evals=1,
                      extras={})
    lib.record(ta, worse)
    assert np.array_equal(lib.lookup(ta)[0], res.best_genome)


def test_server_warm_starts_repeat_queries():
    srv = SweepServer(port=0, config=CFG)
    srv.start_background()
    try:
        list(submit(srv.host, srv.port, task("ws", budget=300)))
        list(submit(srv.host, srv.port, task("ws", budget=300)))
        st = next(iter(sweep_serve.request(
            srv.host, srv.port, {"op": "stats"})))["stats"]
        assert st["library"]["hits"] == 1
        assert st["library"]["misses"] == 1
        assert st["warm_started"] == 1
    finally:
        srv.stop()


def test_standard_es_rejects_runtime_kwargs():
    t = task("se", method="standard_es", budget=300)
    t.runtime_kw["warm_seeds"] = np.zeros((1, 4), dtype=np.int64)
    with pytest.raises(ValueError, match="standard_es"):
        MultiSearch([t], CFG).run()


# --------------------------------------------------- protocol errors


def test_unknown_arch_rejected_with_hint_server_survives():
    srv = SweepServer(port=0, config=CFG)
    srv.start_background()
    try:
        bad = task("ua").to_json_dict()
        bad["platform"] = "clodu"
        evs = list(sweep_serve.request(
            srv.host, srv.port, {"op": "submit", "task": bad}))
        assert not evs[0]["ok"] and evs[0]["unknown_arch"]
        assert "did you mean 'cloud'" in evs[0]["error"]
        # the server is still serving
        evs = list(submit(srv.host, srv.port, task("ua", budget=300)))
        assert any(e.get("event") == "done" for e in evs)
    finally:
        srv.stop()


def test_config_fragment_mismatch_rejected():
    srv = SweepServer(port=0, config=CFG)
    srv.start_background()
    try:
        msg = {"op": "submit", "task": task("cf").to_json_dict(),
               "config": FleetConfig(device_rounds=2).to_json_dict()}
        evs = list(sweep_serve.request(srv.host, srv.port, msg))
        assert not evs[0]["ok"] and "disagrees" in evs[0]["error"]
    finally:
        srv.stop()


# ------------------------------------------------- subprocess smoke


@pytest.mark.slow
def test_sweep_server_subprocess_smoke(subprocess_env):
    """The acceptance scenario end to end, over real sockets and
    processes: server CLI + two concurrent same-signature clients + one
    different-topology client.  The same-signature pair must coalesce
    (their shared signature group holds 2 tasks; dispatches/round stays
    1.0 while only that group runs) and shutdown must be clean."""
    env = subprocess_env()
    srv = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "sweep",
         "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=ROOT)
    try:
        line = srv.stdout.readline()
        assert "listening on" in line, line
        port = int(line.rsplit(":", 1)[1])

        results = {}

        def client(tag, t, delay=0.0):
            time.sleep(delay)
            results[tag] = list(submit("127.0.0.1", port, t))

        threads = [
            threading.Thread(target=client,
                             args=("a", task("sub_a", seed=1))),
            threading.Thread(target=client,
                             args=("b", task("sub_b", seed=2))),
            # different topology => its own signature group
            threading.Thread(target=client,
                             args=("c", task("sub_c", seed=3,
                                             platform="edge"), 0.5)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)

        for tag in ("a", "b", "c"):
            evs = results[tag]
            assert evs[0]["ok"], (tag, evs[0])
            assert any(e.get("event") == "done" for e in evs), (tag, evs)

        st = next(iter(sweep_serve.request(
            "127.0.0.1", port, {"op": "stats"})))["stats"]
        assert st["queries"] == 3 and st["completed"] == 3
        # coalescing evidence: some epoch held the same-signature pair
        # in ONE signature group (server keeps per-epoch group history)
        assert any(max(g.values()) >= 2
                   for g in st["epoch_signature_groups"] if g), \
            f"same-signature queries never shared a group: " \
            f"{st['epoch_signature_groups']}"
        # per-round coalescing: one host sync per fleet round
        assert st["fleet"]["host_syncs_per_round"] == 1.0

        list(sweep_serve.request("127.0.0.1", port, {"op": "shutdown"}))
        assert srv.wait(timeout=60) == 0
        out = srv.stdout.read()
        assert "sweep serve stopped" in out
    finally:
        if srv.poll() is None:
            srv.kill()


@pytest.mark.slow
def test_serve_dispatch_help(subprocess_env):
    """Top-level serve --help names both modes; each mode's --help is
    accurate to its own flags."""
    env = subprocess_env()

    def run(args):
        return subprocess.run([sys.executable, "-m",
                               "repro.launch.serve"] + args,
                              capture_output=True, text=True, env=env,
                              cwd=ROOT, timeout=120)

    top = run(["--help"])
    assert top.returncode == 0
    assert "decode" in top.stdout and "sweep" in top.stdout
    sw = run(["sweep", "--help"])
    assert sw.returncode == 0
    assert "--checkpoint-dir" in sw.stdout
    assert "--batch" not in sw.stdout
    dec = run(["decode", "--help"])
    assert dec.returncode == 0
    assert "--prompt-len" in dec.stdout
    assert "--checkpoint-dir" not in dec.stdout
