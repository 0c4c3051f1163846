"""Cost kernel, scan: device microseconds of the segment scan program
(XLA module ``jit_one_task``) per genome row the scans evaluated in the
traced part of the window (``fleet.rows`` of kind ``scan`` or
``dscan``; ``repro.core.trace``)."""

SCAN_MODULE = "jit_one_task"


def read(ctx):
    tr = ctx.get("trace")
    if not tr or ctx.get("trace_lo") is None:
        return None
    try:
        from repro.core import trace
    except ImportError:             # a program without the recorder
        return None
    evs = trace.events(ctx["trace_lo"], ctx["trace_hi"], {"fleet.rows"})
    if evs is None:
        return None
    rows = sum(e.value for e in evs
               if (e.attrs or {}).get("kind") in ("scan", "dscan"))
    secs = tr["module_s"].get(SCAN_MODULE, 0.0)
    return None if rows <= 0 or secs <= 0 else secs / rows * 1e6
