"""Compile layer: scan programs the program built inside the window,
traced, compiled or loaded at a dispatch or by compile-ahead (its
``fleet.scan_builds`` counter; ``repro.core.trace``).  None for a
program that counts no scan builds."""


def read(ctx):
    try:
        from repro.core import trace
    except ImportError:             # a program without the recorder
        return None
    if "fleet.scan_builds" not in trace.totals():
        return None
    evs = trace.events(ctx["t_open"], ctx["t_close"], {"fleet.scan_builds"})
    return None if evs is None else sum(e.value for e in evs)
