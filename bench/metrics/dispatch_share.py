"""Fleet driver: share of the window the host spent issuing device
dispatches (``fleet.dispatch``: batch preparation, padding, stacked
constants, host-to-device copies and the launch, a wait on an in-flight
compile included), clipped to the window (``repro.core.trace``)."""
from stats import union_length


def read(ctx):
    try:
        from repro.core import trace
    except ImportError:             # a program without the recorder
        return None
    lo, hi = ctx["t_open"], ctx["t_close"]
    evs = trace.events(lo, hi, {"fleet.dispatch"})
    if evs is None or ctx["window_s"] <= 0:
        return None
    return union_length(((e.t0, e.t1) for e in evs), lo, hi) / \
        ctx["window_s"]
