"""ES engine: share of the window the fleet driver spent advancing its
searches on the host (``fleet.advance`` self time: the mega-batch's
results sliced back, budget accounting, the ES operators and the next
batches), with the ``fleet.block`` waits nested in it taken out, clipped
to the window (``repro.core.trace``)."""
from stats import union_length


def read(ctx):
    try:
        from repro.core import trace
    except ImportError:             # a program without the recorder
        return None
    lo, hi = ctx["t_open"], ctx["t_close"]
    evs = trace.events(lo, hi, {"fleet.advance", "fleet.block"})
    if evs is None or ctx["window_s"] <= 0:
        return None
    block = [(e.t0, e.t1) for e in evs if e.name == "fleet.block"]
    # |advance| - |advance and block| = |advance or block| - |block|
    both = union_length(((e.t0, e.t1) for e in evs), lo, hi)
    return (both - union_length(block, lo, hi)) / ctx["window_s"]
