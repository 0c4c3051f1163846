"""Percentile and window arithmetic shared by the metric readers."""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def quantile(values: Iterable[float], q: float) -> Optional[float]:
    """The q-quantile (0 <= q <= 1) by linear interpolation between the
    two nearest ranks (numpy's default); None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile {q} outside [0, 1]")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def union_length(intervals: Iterable[Tuple[float, float]],
                 lo: float = -math.inf, hi: float = math.inf) -> float:
    """Total length covered by the union of [start, end) intervals,
    clipped to [lo, hi)."""
    spans = sorted((max(s, lo), min(e, hi)) for s, e in intervals)
    total = 0.0
    cur_s = cur_e = None
    for s, e in spans:
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The sub-intervals of [lo, hi) that no interval covers, in order."""
    out = []
    cur = lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def progress_in_window(points: Sequence[Tuple[float, float]], lo: float,
                       hi: float) -> float:
    """Work done inside [lo, hi) by a task whose cumulative progress
    passes through ``points`` (time, work done so far), taken as linear
    between consecutive points."""
    done = 0.0
    for (t0, w0), (t1, w1) in zip(points, points[1:]):
        if t1 <= t0 or w1 == w0:
            continue
        ov = min(t1, hi) - max(t0, lo)
        if ov > 0:
            done += (w1 - w0) * ov / (t1 - t0)
    return done


def evals_in_window(ctx, lo: Optional[float] = None,
                    hi: Optional[float] = None) -> float:
    """Evaluations the server performed inside the window (or inside
    [lo, hi) on the same clock), summed over every query (warm-up
    queries in flight included): each query's progress runs from 0 at
    its submission through the ``evals`` of each update to that of its
    ``done``."""
    lo = ctx["t_open"] if lo is None else lo
    hi = ctx["t_close"] if hi is None else hi
    total = 0.0
    for q in ctx["queries"]:
        if q.t_submit is None:
            continue
        pts = [(q.t_submit, 0.0)]
        for ev in q.events:
            if ev.get("event") in ("update", "done") and "evals" in ev:
                pts.append((ev["t_recv"], float(ev["evals"])))
        total += progress_in_window(pts, lo, hi)
    return total
