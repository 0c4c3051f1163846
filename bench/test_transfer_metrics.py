"""CPU checks of the ``transfers_per_batch`` reader on recorder events
planted in a window: the ratio of ``fleet.transfers`` counts to stacked
``fleet.dispatch`` spans, and None where the program counts no transfers,
the window holds no stacked dispatch, or the ring has dropped the
window's start."""
from __future__ import annotations

import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import cell  # noqa: E402

SIG = (3, 32, "cafe0123", "u")
READ = cell.reader("transfers_per_batch")


def _window(stacked=4, uploads=1, other=("bcast", "scan")):
    """Stacked dispatches with one rows copy up and one result copy back
    each, ``uploads`` table copies, and dispatches of other kinds."""
    from repro.core import trace
    t_open = time.perf_counter()
    for i in range(stacked):
        t = time.perf_counter()
        trace.record("fleet.dispatch", t, t + 1e-4, sig=SIG, kind="stacked")
        if i < uploads:
            trace.count("fleet.transfers", dir="h2d", what="table")
        trace.count("fleet.transfers", dir="h2d", what="rows")
        trace.count("fleet.transfers", dir="d2h", what="out")
    for kind in other:
        t = time.perf_counter()
        trace.record("fleet.dispatch", t, t + 1e-4, sig=SIG, kind=kind)
    t_close = time.perf_counter() + 1e-3
    return dict(t_open=t_open, t_close=t_close, window_s=t_close - t_open)


@pytest.mark.parametrize("stacked,uploads", [(4, 1), (8, 0), (1, 1)])
def test_ratio_on_planted_events(stacked, uploads):
    ctx = _window(stacked, uploads)
    assert READ(ctx) == pytest.approx((2 * stacked + uploads) / stacked)


def test_none_without_a_stacked_dispatch():
    assert READ(_window(stacked=0, uploads=0)) is None


def test_none_once_the_ring_dropped_the_window(monkeypatch):
    from repro.core import trace
    ctx = _window()
    monkeypatch.setattr(trace, "_DROPPED_T1", ctx["t_open"])
    assert READ(ctx) is None


def test_none_for_a_program_without_the_counter(monkeypatch):
    """A recorder that never counted ``fleet.transfers``, as a program
    from before the counter: stacked dispatches alone read None."""
    from repro.core import trace
    monkeypatch.setattr(trace, "_TOTALS", {})
    t_open = time.perf_counter()
    t = time.perf_counter()
    trace.record("fleet.dispatch", t, t + 1e-4, sig=SIG, kind="stacked")
    ctx = dict(t_open=t_open, t_close=time.perf_counter() + 1e-3)
    assert "fleet.transfers" not in trace.totals()
    assert READ(ctx) is None
