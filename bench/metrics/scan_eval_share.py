"""Segment scan: share of the genome rows evaluated inside the window
that the device-resident ES scans evaluated (the program's
``fleet.rows`` counters of kind ``scan`` or ``dscan`` over all of them;
``repro.core.trace``).  None for a program whose row counters carry no
kind."""

SCAN_KINDS = ("scan", "dscan")


def read(ctx):
    try:
        from repro.core import trace
    except ImportError:             # a program without the recorder
        return None
    evs = trace.events(ctx["t_open"], ctx["t_close"], {"fleet.rows"})
    if not evs or any("kind" not in (e.attrs or {}) for e in evs):
        return None
    rows = sum(e.value for e in evs)
    scan = sum(e.value for e in evs if e.attrs["kind"] in SCAN_KINDS)
    return None if rows <= 0 else scan / rows
