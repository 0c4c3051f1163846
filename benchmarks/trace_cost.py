"""What the trace recorder (``repro.core.trace``) costs per event.

Times ``trace.span`` (enter and exit, the profiler annotation included)
and ``trace.count`` on one thread, with no profiler session and then
inside one set up as the chip benchmark's traced runs set it up, and
prints one JSON line of nanoseconds per event:

    PYTHONPATH=src python -m benchmarks.trace_cost [--n 100000]

The served path records about 20 events per fleet step, so ns per event
times events per second is the share of the worker thread the recorder
takes.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax

from repro.core import trace


def per_event_ns(n: int) -> dict:
    """ns per ``span`` and per ``count``, best of three passes of n."""
    out = {}
    for what in ("span", "count"):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter_ns()
            if what == "span":
                for _ in range(n):
                    with trace.span("bench.trace_cost", step=1):
                        pass
            else:
                for _ in range(n):
                    trace.count("bench.trace_cost.n", 1, sig=(1, 2))
            best = min(best, (time.perf_counter_ns() - t0) / n)
        out[what] = round(best, 1)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=100_000)
    args = ap.parse_args(argv)
    # a first pass fills the ring: a long-running server records into a
    # full one, and a growing ring costs more per event
    per_event_ns(args.n)
    off = per_event_ns(args.n)
    # the profiler as the chip benchmark runs it (bench/run.py): host
    # events on, Python function tracing off
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            on = per_event_ns(args.n)
        finally:
            jax.profiler.stop_trace()
    print(json.dumps(dict(backend=jax.default_backend(), n=args.n,
                          profiler_off_ns=off, profiler_on_ns=on)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
