"""Fleet driver: share of the rows sent to the device inside the window
that were padding (the program's ``fleet.rows_padded`` over
``fleet.rows`` plus ``fleet.rows_padded`` counters, every signature
together; ``repro.core.trace``)."""


def read(ctx):
    try:
        from repro.core import trace
    except ImportError:             # a program without the recorder
        return None
    evs = trace.events(ctx["t_open"], ctx["t_close"],
                       {"fleet.rows", "fleet.rows_padded"})
    if evs is None:
        return None
    pad = sum(e.value for e in evs if e.name == "fleet.rows_padded")
    rows = sum(e.value for e in evs)
    return None if rows <= 0 else pad / rows
