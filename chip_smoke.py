#!/usr/bin/env python3
"""Bring-up smoke run of the sweep service on a TPU.

    python3 chip_smoke.py              # phases 1-4 on one chip
    python3 chip_smoke.py --chips 4    # only the sharded phase, on every
                                       # visible chip (a 2x2 v5e host)

One process drives everything (a chip belongs to one process): the
sweep server runs in-process and its clients are threads speaking the
wire protocol over real sockets.  Phases:

1. chip arithmetic — the kernel's ceil(log2 x) is exact at x = 2^k;
2. kernel against oracle — random genomes priced on the chip through
   ``JaxCostModel.__call__`` and ``eval_stacked`` agree with the float64
   numpy oracle (``cost_model.check_against_oracle``);
3. served path — four concurrent clients each submit a budget-20,000
   query of the paper's Table III to one ``SweepServer``; all must end
   ``done`` with no failure and no worker restart;
4. device-resident fleet — ``run_method_sweep`` with the backend's
   default ``device_rounds`` (k-generation scan segments), pipelining and
   compile-ahead, checked against the host replay of the same plans;
5. (``--chips 4`` only) sharded mega-batch and sharded segment fleet
   equal their single-device runs, with rows on every device.

Progress goes to stdout; the last line is one JSON object naming the
device.  Exits non-zero, with no such line, when JAX finds no TPU, when
it is run outside the repository, or when any check fails.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

BUDGET = 20_000
N_GENOMES = 512
TOPOLOGIES = ("maple_edge", "cluster_cloud", "systolic_mesh", "quant_edge",
              "eyeriss_like", "sigma_like", "dstc_like")
ORACLE_PAIRS = (
    [(w, "cloud") for w in ("mm1", "mm3", "mm9", "mm13", "conv4")] +
    [(w, a) for a in TOPOLOGIES for w in ("mm3", "conv4")] +
    [("battn1", "cloud")])
QUERIES = (("mm9", "sparsemap", "cloud"), ("mm5", "sparsemap", "cloud"),
           ("mm3", "pso", "cloud"), ("conv4", "sparsemap", "eyeriss_like"))


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileMeter:
    """XLA compiles (count and seconds, cache loads included) and
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.n += 1
                self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return self.n, self.seconds, self.cache_hits


def phase(name: str, meter: CompileMeter, fn, *args) -> None:
    n0, s0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    log(f"== phase {name}")
    fn(*args)
    n1, s1, h1 = meter.snapshot()
    log(f"== phase {name} passed: wall_s={time.perf_counter() - t0:.3f} "
        f"compiles={n1 - n0} compile_s={s1 - s0:.3f} "
        f"cache_hits={h1 - h0}")


# ------------------------------------------------------------- phase 1


def chip_arithmetic() -> None:
    """ceil(log2 x) at exact powers of two, on the device, both as the
    raw jnp expression and as the kernel's metadata-bit helper."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import jax_cost
    ks = np.arange(1, 41)
    x = jnp.asarray(np.ldexp(np.float32(1.0), ks).astype(np.float32))
    raw = np.asarray(jax.jit(lambda v: jnp.ceil(jnp.log2(v)))(x))
    kern = np.asarray(jax.jit(jax_cost._clog2)(x))
    bad_raw = [int(k) for k, r in zip(ks, raw) if r != k]
    bad_kern = [int(k) for k, r in zip(ks, kern) if r != k]
    log(f"ceil(log2(2^k)), k=1..40: raw first wrong k="
        f"{bad_raw[0] if bad_raw else None}, kernel first wrong k="
        f"{bad_kern[0] if bad_kern else None}")
    # integers off the powers of two, against the oracle's float64 form
    # (informational: float32 log2 rounds just above large 2^k)
    ints = np.unique(np.concatenate([
        np.arange(2, 4097),
        np.ldexp(1.0, np.arange(2, 25)) + 1,
        np.ldexp(1.0, np.arange(2, 25)) - 1])).astype(np.float32)
    got = np.asarray(jax.jit(jax_cost._clog2)(jnp.asarray(ints)))
    want = np.asarray([max(1.0, math.ceil(math.log2(max(float(v), 2.0))))
                       for v in ints])
    moved = ints[got != want]
    log(f"kernel ceil(log2) vs float64 oracle on {len(ints)} integers: "
        f"{len(moved)} differ{' e.g. ' + str(moved[:5].tolist()) if len(moved) else ''}")
    check(not bad_kern, f"kernel ceil(log2(2^k)) wrong from k={bad_kern[:1]}")


# ------------------------------------------------------------- phase 2


def kernel_against_oracle(n_genomes: int = N_GENOMES,
                          pairs=ORACLE_PAIRS) -> None:
    """``n_genomes`` uniformly random genomes per pair, and as many
    neighbours of the balanced default design (1-4 of its genes redrawn
    at random).  At the paper's sizes nearly every random genome is an
    invalid design; about half the neighbours are valid, so EDPs are
    compared on hundreds of distinct designs per pair."""
    import numpy as np

    from repro.configs.paper_workloads import by_name
    from repro.core import jax_cost, search
    from repro.core.arch import as_arch
    from repro.core.baselines import fixed_mapping_genes_for_arch
    from repro.core.cost_model import check_against_oracle
    bad = []
    total = 0
    for i, (wname, arch) in enumerate(pairs):
        spec, ev = search.get_evaluator(by_name(wname), arch)
        rng = np.random.default_rng(1000 + i)
        G = spec.random_genomes(rng, 2 * n_genomes)
        base = np.zeros(spec.length, dtype=G.dtype)
        for gi, v in fixed_mapping_genes_for_arch(spec,
                                                  as_arch(arch)).items():
            base[gi] = v
        for row in G[n_genomes:]:
            keep = np.ones(spec.length, dtype=bool)
            keep[rng.choice(spec.length, size=rng.integers(1, 5),
                            replace=False)] = False
            row[keep] = base[keep]
        via_call = check_against_oracle(spec, arch, G, ev(G))
        via_stack = check_against_oracle(
            spec, arch, G, jax_cost.eval_stacked([ev], [G])[0])
        total += via_call.checked + via_stack.checked
        log(f"{wname}@{arch}: checked={via_call.checked} "
            f"both_valid={via_call.both_valid} worst_log10_err "
            f"call={via_call.worst_log10_err!r} "
            f"stacked={via_stack.worst_log10_err!r} disagreements "
            f"call={len(via_call.disagreements)} "
            f"stacked={len(via_stack.disagreements)}")
        bad += [f"{wname}@{arch} call {d}" for d in via_call.disagreements]
        bad += [f"{wname}@{arch} stacked {d}"
                for d in via_stack.disagreements]
    log(f"kernel-vs-oracle rows checked: {total}, disagreements: {len(bad)}")
    check(not bad, "kernel disagrees with the oracle: " + "; ".join(bad[:5]))


# ------------------------------------------------------------- phase 3


def served_path(budget: int = BUDGET, queries=QUERIES) -> None:
    import numpy as np

    from repro.configs.paper_workloads import by_name
    from repro.core import search
    from repro.core.cost_model import check_against_oracle
    from repro.core.search import FleetConfig, SearchTask
    from repro.launch import sweep_serve

    srv = sweep_serve.SweepServer(
        port=0, config=FleetConfig(stack_batches=True, device_rounds=1))
    srv.start_background()
    results = {}

    def client(i, wname, method, arch):
        task = SearchTask(by_name(wname), arch, budget=budget, seed=i,
                          method=method, name=f"q{i}_{wname}_{method}")
        t0 = time.perf_counter()
        first_update = None
        events = []
        try:
            for ev in sweep_serve.submit(srv.host, srv.port, task,
                                         timeout=3000.0):
                events.append(ev)
                if ev.get("event") == "update" and first_update is None:
                    first_update = time.perf_counter() - t0
        finally:
            results[i] = dict(task=task, events=events,
                              first_update_s=first_update,
                              done_s=time.perf_counter() - t0)

    threads = [threading.Thread(target=client, args=(i,) + q)
               for i, q in enumerate(queries)]
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        stats = next(iter(sweep_serve.request(
            srv.host, srv.port, {"op": "stats"})))["stats"]
    finally:
        srv.stop()

    for i in sorted(results):
        r = results[i]
        task, evs = r["task"], r["events"]
        done = [e for e in evs if e.get("event") == "done"]
        failed = [e for e in evs if e.get("event") == "failed"]
        log(f"query {task.name}@{task.platform}: first_update_s="
            f"{r['first_update_s']!r} done_s={r['done_s']!r} "
            f"events={len(evs)}" + (
                f" best_edp={done[0]['best_edp']!r} evals="
                f"{done[0]['evals']} valid_evals={done[0]['valid_evals']}"
                if done else ""))
        check(not failed, f"{task.name} failed: {failed}")
        check(len(done) == 1, f"{task.name} never reached done: {evs[-3:]}")
        d = done[0]
        check(math.isfinite(d["best_edp"]) and d["valid_evals"] > 0,
              f"{task.name}: no valid design ({d})")
        # the reported best design, re-priced by the float64 oracle
        spec, _ = search.get_evaluator(task.workload, task.platform)
        agr = check_against_oracle(
            spec, task.platform, np.asarray([d["best_genome"]]),
            {"valid": [True], "log10_edp": [math.log10(d["best_edp"])]})
        check(not agr.disagreements,
              f"{task.name} best design vs oracle: {agr.disagreements}")
    log(f"server stats: compilations={stats['compilations']} "
        f"dispatches_per_round={stats.get('dispatches_per_round')!r} "
        f"epoch_signature_groups={stats['epoch_signature_groups']} "
        f"epochs={stats['epochs']} restarts={stats['restarts']} "
        f"failed_epochs={stats['failed_epochs']}")
    check(stats["restarts"] == 0 and stats["failed_epochs"] == 0
          and not stats["errors"], f"server caught errors: {stats['errors']}")
    check(stats["completed"] == len(queries),
          f"server completed {stats['completed']} of {len(queries)}")


# ------------------------------------------------------------- phase 4


def device_fleet(budget: int = BUDGET, expect_source: str = "default:tpu"
                 ) -> None:
    import numpy as np

    from repro.configs.paper_workloads import by_name
    from repro.core import search
    from repro.core.cost_model import LOG10_EDP_RTOL

    methods = ["sparsemap", "standard_es"]
    wls = [by_name("mm3"), by_name("mm9")]

    def fleet(device_execute):
        stats = {}
        t0 = time.perf_counter()
        grid = search.run_method_sweep(methods, wls, "cloud", budget=budget,
                                       stats_out=stats,
                                       device_execute=device_execute)
        return grid, stats, time.perf_counter() - t0

    dev, st, dev_s = fleet(True)
    k = st["device_rounds"]
    log(f"device fleet: wall_s={dev_s!r} device_rounds={k} "
        f"source={st['device_rounds_source']} rounds={st['rounds']} "
        f"host_syncs={st['host_syncs']} host_syncs_per_round="
        f"{st['host_syncs_per_round']!r} dispatches={st['dispatches']} "
        f"compile_ahead hits={st['compile_ahead_hits']} "
        f"misses={st['compile_ahead_misses']} "
        f"errors={st['compile_ahead_errors']} "
        f"host_blocked_s={st['host_blocked_s']!r}")
    check(st["device_rounds_source"] == expect_source,
          f"device_rounds resolved from {st['device_rounds_source']}")
    # the scan-segment phase syncs once per k generations; a segment cut
    # short by the budget may cover fewer
    check(st["host_syncs_per_round"] <= 1.0 / k + 1e-9 or k == 1,
          f"host_syncs_per_round {st['host_syncs_per_round']} > 1/{k}")
    check(st["compile_ahead_misses"] == 0,
          f"{st['compile_ahead_misses']} compile-ahead misses")
    check(st["compile_ahead_errors"] == 0,
          f"compile-ahead errors: {st['compile_ahead_first_error']}")

    host, st_h, host_s = fleet(False)
    log(f"host replay: wall_s={host_s!r} rounds={st_h['rounds']} "
        f"host_syncs={st_h['host_syncs']}")
    for m in methods:
        for wl in wls:
            a, b = dev[m][wl.name], host[m][wl.name]
            same = (a.best_edp == b.best_edp and
                    np.array_equal(a.history, b.history))
            log(f"{m}/{wl.name}: device best_edp={a.best_edp!r} "
                f"host best_edp={b.best_edp!r} evals={a.evals}/{b.evals} "
                f"valid={a.valid_evals}/{b.valid_evals} "
                f"bit_identical={same}")
            if a.best_edp == b.best_edp:
                continue                # bit-equal, or both found nothing
            check(math.isfinite(a.best_edp) and math.isfinite(b.best_edp),
                  f"{m}/{wl.name}: only one run found a valid design")
            la, lb = math.log10(a.best_edp), math.log10(b.best_edp)
            check(abs(la - lb) <= LOG10_EDP_RTOL * max(abs(la), 1.0),
                  f"{m}/{wl.name}: device {a.best_edp} vs host replay "
                  f"{b.best_edp}")


# ------------------------------------------------------------- phase 5


def sharded(ndev: int) -> None:
    """The forced-multi-device parity checks, on real chips: a sharded
    mega-batch and an 8-task sharded segment fleet equal their
    single-device runs bit for bit, and the rows land on every chip."""
    import numpy as np

    from repro.configs.paper_workloads import by_name
    from repro.core import jax_cost, search
    from repro.launch.mesh import make_search_mesh

    mesh = make_search_mesh()
    check(mesh is not None and np.asarray(mesh.devices).size == ndev,
          f"need a {ndev}-device mesh, have {mesh}")
    spec, ev = search.get_evaluator(by_name("mm1"), "cloud")
    rng = np.random.default_rng(0)
    batches = [spec.random_genomes(rng, n) for n in (48, 50, 64)]
    models = [ev] * len(batches)
    plain = jax_cost.eval_stacked(models, batches)
    pending = jax_cost.eval_stacked(models, batches, mesh=mesh, defer=True)
    placed = pending._out    # the (3, rows) device result, unharvested
    shard_devs = [s.device for s in placed.addressable_shards]
    rows = [s.data.shape[1] for s in placed.addressable_shards]
    log(f"sharded eval_stacked: {len(set(shard_devs))} devices, "
        f"rows per device {rows}")
    check(len(set(shard_devs)) == ndev and min(rows) > 0,
          f"mega-batch rows not spread over {ndev} devices: {shard_devs}")
    for p, s in zip(plain, pending.finalize()):
        for key in p:
            check(np.array_equal(p[key], s[key]),
                  f"sharded eval_stacked differs in {key}")

    def fleet(m):
        tasks = [search.SearchTask(by_name("mm1"), "cloud", budget=700,
                                   seed=s, name=f"t{s}") for s in range(8)]
        ms = search.MultiSearch(tasks, search.FleetConfig(
            stack_batches=True, device_rounds=4, mesh=m))
        return ms.run(), ms.stats

    res1, st1 = fleet(None)
    resn, stn = fleet(mesh)
    sharded_scans = [k for k in jax_cost._JIT_FNS
                     if k[4].startswith("scan:") and k[4].endswith(f"@{ndev}")]
    log(f"sharded segment fleet: devices={stn['devices']} "
        f"host_syncs_per_round={stn['host_syncs_per_round']!r} "
        f"sharded scan programs={len(sharded_scans)}")
    check(stn["devices"] == ndev and st1["devices"] == 1,
          "fleet device counts")
    check(sharded_scans, "no sharded scan program ran")
    check(stn["host_syncs_per_round"] <= 0.25, "host syncs per round")
    for name in res1:
        check(res1[name].best_edp == resn[name].best_edp and
              np.array_equal(res1[name].history, resn[name].history),
              f"sharded segment fleet differs for {name}")


# ---------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="1: phases 1-4 on one chip; N>1: only the "
                         "sharded phase, over N visible chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU ({dev.platform} found)", file=sys.stderr)
        return 1
    log(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {cache_dir}")
    meter = CompileMeter()
    t0 = time.perf_counter()
    if args.chips > 1:
        check(len(devices) == args.chips,
              f"--chips {args.chips} but {len(devices)} devices visible")
        phase("5 sharded", meter, sharded, args.chips)
    else:
        phase("1 chip arithmetic", meter, chip_arithmetic)
        phase("2 kernel against oracle", meter, kernel_against_oracle)
        phase("3 served path", meter, served_path)
        phase("4 device-resident fleet", meter, device_fleet)
    n, secs, hits = meter.snapshot()
    log(f"total: wall_s={time.perf_counter() - t0:.3f} compiles={n} "
        f"compile_s={secs:.3f} cache_hits={hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
