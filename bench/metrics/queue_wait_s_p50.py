"""Front end: median seconds a window query waited in the sweep server
between arriving and its admission into a fleet (the program's
``serve.queue`` intervals, ``repro.core.trace``), over the queries that
arrived inside the window: the inside view of ``admit_wait_s_p50``."""
from stats import quantile


def read(ctx):
    try:
        from repro.core import trace
    except ImportError:             # a program without the recorder
        return None
    evs = trace.events(ctx["t_open"], ctx["t_close"], {"serve.queue"})
    if evs is None:
        return None
    return quantile((e.t1 - e.t0 for e in evs if e.t0 >= ctx["t_open"]),
                    0.5)
