"""The comparison that decides `correct`.

Every design the server reported for a query sent inside the window (in
its ``update`` and ``done`` events) is priced again by the float64
reference (``reference.py``), which shares no code with the program.
Numbers compared, each against its limit in the configuration file:

* ``edp_gap_log10`` — the widest gap |log10 EDP reported - log10 EDP of
  the reference| over the designs;
* ``invalid_designs`` — designs reported with a finite EDP that the
  reference finds invalid, other than by a capacity margin thinner than
  ``CAPACITY_MARGIN`` (float32 and float64 occupancies may fall on
  either side of a capacity there);
* ``evals_short`` — ``done`` events whose ``evals`` is not the budget;
* ``missing`` — window queries that failed or never reached ``done``.

A run with no design to compare is not correct.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from reference import Reference, Shape

CAPACITY_MARGIN = 5e-3

Design = Tuple[str, Tuple[int, ...], float]   # (workload, genome, edp)


def reported_designs(queries: Iterable) -> List[Design]:
    """Distinct (workload, genome, reported EDP) of every update and done
    event with a finite EDP, over the given queries."""
    seen = set()
    out = []
    for q in queries:
        for ev in q.events:
            g, edp = ev.get("best_genome"), ev.get("best_edp")
            if g is None or edp is None or not math.isfinite(edp):
                continue
            key = (q.workload, tuple(int(x) for x in g), float(edp))
            if key not in seen:
                seen.add(key)
                out.append(key)
    return out


class Pricer:
    """Reference prices of designs, memoized per (workload, genome)."""

    def __init__(self, config: Dict, rounding: Optional[Callable] = None):
        kw = {} if rounding is None else {"rounding": rounding}
        self.ref = Reference(config["accelerator"], **kw)
        self.shapes = {e["name"]: Shape(e) for e in config["workloads"]}
        self._memo: Dict = {}

    def price(self, workload: str, genome: Tuple[int, ...]) -> Dict:
        key = (workload, genome)
        if key not in self._memo:
            try:
                self._memo[key] = self.ref.price(self.shapes[workload],
                                                 genome)
            except ValueError as e:          # malformed genome
                self._memo[key] = dict(valid=False, why=str(e), margin=1.0)
        return self._memo[key]


def compare(designs: List[Design], pricer: Pricer,
            gap_limit: float = math.inf,
            problems: Optional[List[Dict]] = None
            ) -> Tuple[float, int, int]:
    """(widest log10 EDP gap, designs the reference rejects, designs
    compared) for reported designs against the reference; designs
    rejected or over ``gap_limit`` are appended to ``problems``."""
    gap, invalid, n = 0.0, 0, 0
    for wl, g, edp in designs:
        r = pricer.price(wl, g)
        if not r["valid"] and not (r.get("why") == "capacity" and
                                   r["margin"] < CAPACITY_MARGIN):
            invalid += 1
            if problems is not None:
                problems.append(dict(workload=wl, genome=list(g),
                                     reported_edp=edp, reference=r))
            continue
        n += 1
        d = abs(math.log10(edp) - math.log10(r["edp"]))
        d = math.inf if math.isnan(d) else d
        if d > gap_limit and problems is not None:
            problems.append(dict(workload=wl, genome=list(g),
                                 reported_edp=edp, reference=r, gap=d))
        gap = max(gap, d)
    return gap, invalid, n


def evaluate(config: Dict, window_queries: List, pricer: Pricer) -> Dict:
    """Every number compared, with its limit, and the verdict."""
    limits = config["limits"]
    designs = reported_designs(q for q in window_queries
                               if q.status == "done")
    problems: List[Dict] = []
    gap, invalid, n = compare(designs, pricer, limits["edp_gap_log10"],
                              problems)
    short = sum(1 for q in window_queries if q.status == "done"
                for ev in q.events if ev.get("event") == "done"
                and int(ev.get("evals", -1)) != int(config["budget"]))
    missing = sum(1 for q in window_queries if q.status != "done")
    checks = {
        "edp_gap_log10": {"value": gap,
                          "limit": limits["edp_gap_log10"]},
        "invalid_designs": {"value": invalid, "limit": 0},
        "evals_short": {"value": short, "limit": 0},
        "missing": {"value": missing, "limit": 0},
        "designs_compared": {"value": n, "limit": 1, "at_least": True},
    }
    ok = all((c["value"] >= c["limit"]) if c.get("at_least")
             else (c["value"] <= c["limit"]) for c in checks.values())
    return dict(correct=bool(ok and window_queries), checks=checks,
                problems=problems)
