"""Segment scan: host-device copies per scan dispatch, the program's
``fleet.scan_transfers`` counters (packed rows and first-segment
carries up, packed results back) over its ``fleet.dispatch`` spans of
kind ``scan`` or ``dscan``, both inside the window
(``repro.core.trace``).  None for a program that counts no scan
transfers, or a window with no scan dispatch."""

SCAN_KINDS = ("scan", "dscan")


def read(ctx):
    try:
        from repro.core import trace
    except ImportError:             # a program without the recorder
        return None
    if "fleet.scan_transfers" not in trace.totals():
        return None
    evs = trace.events(ctx["t_open"], ctx["t_close"],
                       {"fleet.scan_transfers", "fleet.dispatch"})
    if evs is None:
        return None
    scans = sum(1 for e in evs if e.name == "fleet.dispatch" and
                (e.attrs or {}).get("kind") in SCAN_KINDS)
    copies = sum(e.value for e in evs if e.name == "fleet.scan_transfers")
    return copies / scans if scans else None
