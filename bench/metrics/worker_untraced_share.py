"""Fleet driver: share of the window in which the sweep server's worker
thread was in none of its leaf spans (``serve.wait``, ``fleet.start``,
``serve.admit``, ``fleet.dispatch``, ``fleet.block``, ``fleet.advance``,
``serve.emit``): what the program's own trace leaves unexplained
(``repro.core.trace``)."""
from stats import union_length

LEAVES = {"serve.wait", "fleet.start", "serve.admit", "fleet.dispatch",
          "fleet.block", "fleet.advance", "serve.emit"}


def read(ctx):
    try:
        from repro.core import trace
    except ImportError:             # a program without the recorder
        return None
    lo, hi = ctx["t_open"], ctx["t_close"]
    evs = trace.events(lo, hi, LEAVES)
    if evs is None or ctx["window_s"] <= 0:
        return None
    return 1.0 - union_length(((e.t0, e.t1) for e in evs), lo, hi) / \
        ctx["window_s"]
