"""In-process trace recorder: spans, cross-thread intervals and counters
of the served path, on the host's ``time.perf_counter`` clock.

    from repro.core import trace
    with trace.span("fleet.advance", step=7):
        ...
    trace.record("serve.queue", t_arrive, t_admit, query=12)
    trace.count("fleet.rows", 1536, sig=sig)

Every event goes into one bounded ring (:data:`CAPACITY` entries, the
oldest dropped first) under the recorder's own lock, and into cumulative
per-name totals that are never dropped (:func:`totals`).  A ``span``
also enters ``jax.profiler.TraceAnnotation(name)``, so with a profiler
session active the same interval lands in the trace's host plane on the
device trace's clock; with none active the annotation does nothing.

Readers select events with :func:`events`, which returns ``None`` when
the ring has already dropped an event that could reach into the asked
window: a truncated record never reads as a smaller share.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

#: ring size: at ~1,500 events/s it holds the last three minutes
CAPACITY = 1 << 18

_LOCK = threading.Lock()
# (name, t0, t1, thread, value, attrs); value is None for intervals
_RING: deque = deque(maxlen=CAPACITY)
# name -> [events, seconds, value], cumulative since process start
_TOTALS: Dict[str, List] = {}
# latest end time of any event the ring has dropped
_DROPPED_T1 = float("-inf")


class Event(NamedTuple):
    name: str
    t0: float
    t1: float
    thread: str
    value: Optional[float]      # a counter's increment; None otherwise
    attrs: Optional[Dict]


def _add(name: str, t0: float, t1: float, value, attrs) -> None:
    global _DROPPED_T1
    ev = (name, t0, t1, threading.current_thread().name, value,
          attrs or None)
    with _LOCK:
        if len(_RING) == CAPACITY:
            _DROPPED_T1 = max(_DROPPED_T1, _RING[0][2])
        _RING.append(ev)
        tot = _TOTALS.get(name)
        if tot is None:
            tot = _TOTALS[name] = [0, 0.0, None]
        tot[0] += 1
        if value is None:
            tot[1] += t1 - t0
        else:
            tot[2] = value if tot[2] is None else tot[2] + value


class span:
    """Context manager recording ``(name, t0, t1, thread, attrs)`` on
    exit, and a profiler annotation of the same name around the body."""

    __slots__ = ("name", "attrs", "t0", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _add(self.name, self.t0, t1, None, self.attrs)


def name_os_thread(name: str) -> None:
    """Give the calling thread an operating-system name of its own
    (Linux; 15 bytes at most).  Python threads otherwise all carry the
    process's name, which a profiler trace gives their per-thread lines,
    so a reader that keys lines by name keeps one thread's spans and
    loses the others'.  Call it first thing in the thread."""
    pr_set_name = 15
    try:
        import ctypes
        ctypes.CDLL(None).prctl(pr_set_name, name.encode()[:15], 0, 0, 0)
    except (OSError, AttributeError):
        pass


def record(name: str, t0: float, t1: float, **attrs) -> None:
    """An interval measured elsewhere, e.g. begun on one thread and
    ended on another (in memory only: no profiler annotation)."""
    _add(name, t0, t1, None, attrs)


def count(name: str, n=1, **attrs) -> None:
    """A counter increment: a timestamped event and the running total."""
    t = time.perf_counter()
    _add(name, t, t, n, attrs)


def total(name: str):
    """The cumulative seconds of an interval name, or the cumulative
    value of a counter; 0 for a name never recorded."""
    with _LOCK:
        tot = _TOTALS.get(name)
        if tot is None:
            return 0
        return tot[1] if tot[2] is None else tot[2]


def totals() -> Dict[str, Dict]:
    """``{name: {"count": events, "seconds": s}}`` for intervals and
    ``{name: {"count": events, "value": v}}`` for counters, cumulative
    since process start."""
    with _LOCK:
        items = [(k, list(v)) for k, v in _TOTALS.items()]
    return {k: ({"count": c, "seconds": s} if v is None
                else {"count": c, "value": v})
            for k, (c, s, v) in sorted(items)}


def events(t0: float, t1: float,
           names: Optional[set] = None) -> Optional[List[Event]]:
    """The events that overlap ``[t0, t1)`` (an instant at ``t`` when
    ``t0 <= t < t1``), in recording order, optionally only those named
    in ``names``; None when the ring has dropped an event ending at or
    after ``t0``."""
    with _LOCK:
        if _DROPPED_T1 >= t0:
            return None
        held = list(_RING)
    return [Event(*e) for e in held
            if (names is None or e[0] in names) and e[1] < t1 and
            (e[2] > t0 or e[1] >= t0)]
