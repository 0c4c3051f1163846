#!/usr/bin/env python3
"""The benchmark: one cell of BENCHMARK.json on the chips of this machine.

    python3 bench/run.py --workload t3_spmm_cloud.es --seed 7 \
        --seconds 30 --trace 0

One process holds the chip and runs everything: the program's sweep
server (``repro.launch.sweep_serve.SweepServer``, configured from the
cell's configuration file) and the closed-loop clients of the cell's
traffic mix, which submit ``SearchTask`` queries over real sockets.
Set-up runs the cell's own traffic under a warm-up seed until every
client has finished its warm-up queries and nothing has compiled for a
while; the window then opens on that running load and measures for
``--seconds``; queries still in flight at the close are drained; every design the server reported for a
window query is priced again by the float64 reference
(``reference.py``) to decide ``correct``.  With ``--trace 1`` the last
``TRACE_S`` seconds of the window run under the JAX profiler and the line
carries the per-layer metrics.

The last line of standard output is the JSON result.  Without a TPU, or
with fewer chips than the cell asks for, or on a device missing from
``devices.json``, the run exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".bench_cache")
#: the warm-up may take this long before the run gives up
WARMUP_LIMIT_S = 600.0
#: a traced run traces the last seconds of its window, this many at most:
#: stopping the profiler takes about three seconds per traced second
TRACE_S = 20.0
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from cell import CellError, load_cell, read_metrics  # noqa: E402


def log(msg: str) -> None:
    """Progress on standard error, stamped with seconds since the start."""
    print(f"[{time.perf_counter() - T_START:8.2f}] {msg}", file=sys.stderr,
          flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True,
                    help="cell name from BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_device(chips: int) -> Optional[Dict]:
    """The accelerator the run may use, or None (with the reason on
    standard error): a TPU of a kind ``devices.json`` lists, with at
    least ``chips`` chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devs[0].platform})",
              file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"bench: cell needs {chips} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        return None
    with open(os.path.join(BENCH, "devices.json")) as f:
        peaks = json.load(f)["devices"]
    kind = devs[0].device_kind
    if kind not in peaks:
        print(f"bench: device kind {kind!r} is not in devices.json",
              file=sys.stderr)
        return None
    return dict(platform=devs[0].platform, kind=kind, count=chips,
                devices=devs[:chips])


def build_workloads(config: Dict) -> Dict:
    """The configuration's Table III entries as the program's workloads."""
    from repro.core.density import BlockNM
    from repro.core.workload import spconv, spmm

    def dens(e, t):
        s = e.get(f"structured_{t}")
        return BlockNM(s["n"], s["m"]) if s else \
            e[f"density_{t}_pct"] / 100.0

    out = {}
    for e in config["workloads"]:
        if e["kind"] == "spmm":
            out[e["name"]] = spmm(e["name"], e["M"], e["K"], e["N"],
                                  dens(e, "P"), dens(e, "Q"))
        else:
            out[e["name"]] = spconv(
                e["name"], e["C"], e["H"], e["W"], e["Kout"], e["R"],
                e["S"], e["density_input_pct"] / 100.0,
                e["density_weight_pct"] / 100.0)
    return out


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        try:
            peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
        except (KeyError, TypeError, RuntimeError):
            pass
    return peak


class Session:
    """One process's sweep server with everything warmed, able to run a
    measured window (several, for the control readings)."""

    def __init__(self, cell: Dict, device: Dict):
        from repro.core.search import FleetConfig
        from repro.launch import sweep_serve
        from repro.launch.compile_cache import enable_compile_cache

        import loadgen
        from clients import CompileMeter

        enable_compile_cache()
        self.meter = CompileMeter()
        self.cell, self.device = cell, device
        self.cfg = cfg = cell["config"]
        self.tp = tp = loadgen.traffic_params(cell["traffic"])
        self.arch = cfg["accelerator"]["name"]
        self.budget = int(cfg["budget"])
        self.wls = build_workloads(cfg)
        fleet = FleetConfig(**cfg["fleet"])
        log(f"set-up: JAX ready with {len(device['devices'])} device(s)")
        self.srv = sweep_serve.SweepServer(
            port=0, config=fleet, warm_start=bool(cfg["warm_start"]))
        self.srv.start_background()

    def _task(self, q, name):
        from repro.core.search import SearchTask
        return SearchTask(self.wls[q.workload], self.arch,
                          budget=self.budget, seed=q.seed, method=q.method,
                          name=name)

    def _submit(self, task):
        from repro.launch import sweep_serve
        return sweep_serve.submit(self.srv.host, self.srv.port, task,
                                  timeout=900.0)

    def window(self, seed: int, seconds: float, trace: bool) -> Dict:
        """The traffic under the warm-up seed until it is warm, then the
        measured window on the same running load; returns the metric
        context, with queries still in flight at the close drained."""
        import jax
        from repro.core import jax_cost

        import devtrace
        import loadgen
        from clients import ClosedLoop, wait_until_warm

        names = [e["name"] for e in self.cfg["workloads"]]
        loop = ClosedLoop(
            self._submit, self._task,
            loadgen.streams(names, self.tp, loadgen.WARMUP_SEED),
            loadgen.streams(names, self.tp, seed))
        trace_dir = os.path.join(CACHE, "trace")
        loop.start()
        try:
            wait_until_warm(loop, self.meter, self.tp["warmup_queries"],
                            self.tp["warmup_quiet_s"], WARMUP_LIMIT_S)
        except RuntimeError:
            loop.open_window(0.0)        # the clients send nothing more
            loop.drain(time.perf_counter() + self.tp["drain_seconds"])
            raise
        log(f"set-up: warm after {len(loop.queries)} warm-up queries "
            f"(compiles and cache loads {self.meter.snapshot()[0]})")
        c0 = (self.meter.snapshot(), jax_cost.dispatch_count(),
              jax_cost.host_blocked_s())
        loop.open_window(seconds)
        setup_s = loop.t_open - T_START
        trace_lo = trace_hi = None
        if trace:
            time.sleep(max(0.0, loop.t_close - min(seconds, TRACE_S)
                           - time.perf_counter()))
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            trace_lo, start_epoch_ns = time.perf_counter(), time.time_ns()
        time.sleep(max(0.0, loop.t_close - time.perf_counter()))
        c1 = (self.meter.snapshot(), jax_cost.dispatch_count(),
              jax_cost.host_blocked_s())
        red = None
        if trace:
            trace_hi = time.perf_counter()
            jax.profiler.stop_trace()
            log("trace stopped")
            path = devtrace.latest_xplane(trace_dir)
            if path is not None:
                tr = devtrace.load(path)
                log(f"trace read: {os.path.getsize(path)} bytes")
                lo = start_epoch_ns - (tr["profile_start_ns"] or
                                       start_epoch_ns)
                red = devtrace.reduce(tr, lo, lo + (trace_hi - trace_lo) * 1e9)
            shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"window closed: {len(loop.window_queries())} queries sent, "
            f"{c1[0][0] - c0[0][0]} compiles and cache loads inside")
        drained = loop.drain(loop.t_close + self.tp["drain_seconds"])
        log(f"drained: {drained}")
        return dict(queries=loop.queries,
                    window_queries=loop.window_queries(),
                    t_open=loop.t_open, t_close=loop.t_close,
                    window_s=loop.t_close - loop.t_open, setup_s=setup_s,
                    compiles=c1[0][0] - c0[0][0],
                    compile_s=c1[0][1] - c0[0][1],
                    dispatches=c1[1] - c0[1],
                    host_blocked_s=c1[2] - c0[2], trace=red,
                    trace_lo=trace_lo, trace_hi=trace_hi,
                    drained=drained)

    def close(self) -> None:
        self.srv.stop()


def run_cell(cell: Dict, seed: int, seconds: float, trace: bool,
             device: Dict) -> Dict:
    """Set up, measure, drain and check one cell; returns the result."""
    import check

    sess = Session(cell, device)
    try:
        ctx = sess.window(seed, seconds, trace)
        peak = memory_peak(device["devices"])
    finally:
        sess.close()
    cfg, window, red = cell["config"], ctx["window_queries"], ctx["trace"]
    t0 = time.perf_counter()
    verdict = check.evaluate(cfg, window, check.Pricer(cfg))
    log(f"checked {verdict['checks']['designs_compared']['value']} designs "
        f"in {time.perf_counter() - t0:.1f} s")
    metrics = read_metrics(cell["per_layer"] if trace
                           else cell["end_to_end"], ctx)
    dev = dict(platform=device["platform"], kind=device["kind"],
               count=device["count"], memory_peak_bytes=peak)
    if trace:
        dev["busy_s"] = red["busy_s"] if red else 0.0
        dev["window_s"] = red["window_s"] if red else seconds
    out = dict(correct=verdict["correct"] and ctx["drained"],
               attempted=len(window),
               failed=sum(1 for q in window if q.status != "done"),
               metrics=metrics, device=dev)
    if red:
        out["breakdown"] = dict(device_ops=red["device_ops"],
                                idle_gaps=red["idle_gaps"])
    out["checks"] = verdict["checks"]
    out["problems"] = verdict["problems"]
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    # the persistent compilation cache lives in the checkout, at a fixed
    # path; the program's entry points take it from this variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    device = find_device(cell["chips"])
    if device is None:
        return 1
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device)
    for p in result.pop("problems")[:20]:
        print(f"bench: design at fault: {json.dumps(p)}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit "
              f"{'>=' if c.get('at_least') else '<='} {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
