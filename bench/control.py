#!/usr/bin/env python3
"""Readings for the limits of `correct`: the program's and the control's.

    python3 bench/control.py --workload t3_spmm_cloud.es --seed 100 \
        --seeds 12 --seconds 30

One process sets the cell up once, then runs one measured window per
seed (``--seed``, ``--seed`` + 1, ...) at the cell's own load.  For each
window it prints one JSON line with the program's numbers (what a run
compares) and the control's: the same reported designs priced by the
reference computed in bfloat16, the precision below the float32 that
the configuration states, put in the program's place.  The control has
to fail the ``edp_gap_log10`` limit; the program has to pass it.  The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def readings(cfg, window_queries, pricer=None, control_pricer=None):
    """The program's numbers and the control's gap on one window."""
    import check
    from reference import bf16
    pricer = pricer or check.Pricer(cfg)
    control_pricer = control_pricer or check.Pricer(cfg, rounding=bf16)
    verdict = check.evaluate(cfg, window_queries, pricer)
    designs = check.reported_designs(q for q in window_queries
                                     if q.status == "done")
    gap, _, n = check.compare(
        [(wl, g, control_pricer.price(wl, g).get("edp", float("inf")))
         for wl, g, _ in designs if pricer.price(wl, g)["valid"]],
        pricer)
    return dict(program={k: c["value"]
                         for k, c in verdict["checks"].items()},
                control={"edp_gap_log10": gap, "designs_compared": n},
                limit=cfg["limits"]["edp_gap_log10"])


def main(argv=None) -> int:
    import run
    from cell import load_cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(run.CACHE, "jax")
    device = run.find_device(cell["chips"])
    if device is None:
        return 1
    import check
    from reference import bf16
    cfg = cell["config"]
    pricer, cpricer = check.Pricer(cfg), check.Pricer(cfg, rounding=bf16)
    sess = run.Session(cell, device)
    try:
        for i in range(args.seeds):
            ctx = sess.window(args.seed + i, args.seconds, False)
            r = readings(cfg, ctx["window_queries"], pricer, cpricer)
            r.update(seed=args.seed + i, drained=ctx["drained"],
                     window_compiles=ctx["compiles"])
            print(json.dumps(r), flush=True)
    finally:
        sess.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
