"""Host prologue: median seconds from a window query's admission into a
fleet (the end of its ``serve.queue`` interval) to its search's
``es.phase`` mark ``main`` (calibration, HSHI and LHS done), over the
queries that arrived inside the window and reached that mark: the inside
view of ``first_update_s_p50`` (``repro.core.trace``)."""
import math

from stats import quantile


def read(ctx):
    try:
        from repro.core import trace
    except ImportError:             # a program without the recorder
        return None
    # a query sent near the close may reach its main loop in the drain
    evs = trace.events(ctx["t_open"], math.inf, {"serve.queue", "es.phase"})
    if evs is None:
        return None
    admitted = {e.attrs["query"]: e.t1 for e in evs
                if e.name == "serve.queue" and
                ctx["t_open"] <= e.t0 < ctx["t_close"]}
    main = {}
    for e in evs:
        q = e.attrs.get("query") if e.name == "es.phase" else None
        if q in admitted and e.attrs["phase"] == "main" and q not in main \
                and e.t0 >= admitted[q]:
            main[q] = e.t0 - admitted[q]
    return quantile(main.values(), 0.5)
