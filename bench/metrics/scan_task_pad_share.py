"""Segment scan: share of the scan dispatches' task slots inside the
window that were filler, Σ(slots − tasks) over Σ slots of the
program's ``fleet.scan_tasks`` counters (``repro.core.trace``)."""


def read(ctx):
    try:
        from repro.core import trace
    except ImportError:             # a program without the recorder
        return None
    evs = trace.events(ctx["t_open"], ctx["t_close"], {"fleet.scan_tasks"})
    if not evs:
        return None
    slots = sum(e.attrs["slots"] for e in evs)
    return (slots - sum(e.value for e in evs)) / slots
