"""Fleet driver: host-device copies per stacked mega-batch, the
program's ``fleet.transfers`` counters (rows and constants tables up,
results back) over its ``fleet.dispatch`` spans of kind ``stacked``, both
inside the window (``repro.core.trace``).  None for a program that counts
no transfers, or a window with no stacked dispatch."""


def read(ctx):
    try:
        from repro.core import trace
    except ImportError:             # a program without the recorder
        return None
    if "fleet.transfers" not in trace.totals():
        return None
    evs = trace.events(ctx["t_open"], ctx["t_close"],
                       {"fleet.transfers", "fleet.dispatch"})
    if evs is None:
        return None
    batches = sum(1 for e in evs if e.name == "fleet.dispatch" and
                  (e.attrs or {}).get("kind") == "stacked")
    copies = sum(e.value for e in evs if e.name == "fleet.transfers")
    return copies / batches if batches else None
