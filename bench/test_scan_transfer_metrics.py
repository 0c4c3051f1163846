"""CPU checks of the ``scan_transfers_per_dispatch`` reader on recorder
events planted in a window: the ratio of ``fleet.scan_transfers`` counts
to ``fleet.dispatch`` spans of kind ``scan`` or ``dscan`` (stacked
dispatches and their ``fleet.transfers`` left out), and None where the
program counts no scan transfers, the window holds no scan dispatch, or
the ring has dropped the window's start."""
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
READ = cell.reader("scan_transfers_per_dispatch")


def _window(scans=4, direct=0, carries=3, stacked=2):
    """Scan dispatches with one packed rows copy up and one result copy
    back each, ``carries`` first-segment carries beside them, and
    stacked dispatches with their own copies."""
    from repro.core import trace
    t_open = time.perf_counter()
    for i in range(scans + direct):
        t = time.perf_counter()
        trace.record("fleet.dispatch", t, t + 1e-4, sig=SIG,
                     kind="scan" if i < scans else "dscan")
        trace.count("fleet.scan_transfers", dir="h2d", what="rows")
        trace.count("fleet.scan_transfers", dir="d2h", what="out")
    for _ in range(carries):
        trace.count("fleet.scan_transfers", dir="h2d", what="carry")
    for _ in range(stacked):
        t = time.perf_counter()
        trace.record("fleet.dispatch", t, t + 1e-4, sig=SIG, kind="stacked")
        trace.count("fleet.transfers", dir="h2d", what="rows")
        trace.count("fleet.transfers", dir="d2h", what="out")
    trace.count("fleet.transfers", dir="h2d", what="table")
    t_close = time.perf_counter() + 1e-3
    return dict(t_open=t_open, t_close=t_close, window_s=t_close - t_open)


@pytest.mark.parametrize("scans,direct,carries", [
    (4, 0, 3), (8, 0, 0), (1, 1, 2), (0, 3, 1)])
def test_ratio_on_planted_events(scans, direct, carries):
    ctx = _window(scans, direct, carries)
    assert READ(ctx) == pytest.approx(
        (2 * (scans + direct) + carries) / (scans + direct))


def test_none_without_a_scan_dispatch():
    assert READ(_window(scans=0, carries=0)) is None


def test_none_once_the_ring_dropped_the_window(monkeypatch):
    from repro.core import trace
    ctx = _window()
    monkeypatch.setattr(trace, "_DROPPED_T1", ctx["t_open"])
    assert READ(ctx) is None


def test_none_for_a_program_without_the_counter(monkeypatch):
    """A recorder that never counted ``fleet.scan_transfers``, as a
    program from before the counter (the parent of the packed scan): scan
    dispatches alone read None."""
    from repro.core import trace
    monkeypatch.setattr(trace, "_TOTALS", {})
    t_open = time.perf_counter()
    t = time.perf_counter()
    trace.record("fleet.dispatch", t, t + 1e-4, sig=SIG, kind="scan")
    trace.count("fleet.transfers", dir="h2d", what="table")
    ctx = dict(t_open=t_open, t_close=time.perf_counter() + 1e-3)
    assert "fleet.scan_transfers" not in trace.totals()
    assert READ(ctx) is None
