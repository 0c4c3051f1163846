"""Closed-loop socket clients of the sweep server, and the compile meter.

The clients are threads of the run's own process (a chip belongs to one
process) speaking the server's wire protocol over real sockets.  Each
client sends its next query when the previous one reaches ``done`` or
``failed``.  Every event is kept with its arrival time on the host
clock, for the latency metrics and for the check of every design the
server reported.  The compile meter tells the warm-up when the traffic
has stopped compiling.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

#: JAX monitoring event: one per backend compile or persistent-cache load
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    """XLA compiles (count and seconds, cache loads included), from JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        self.last: Optional[float] = None   # host clock of the last event
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.n += 1
                self.seconds += duration
                self.last = time.perf_counter()

    def snapshot(self):
        with self._lock:
            return self.n, self.seconds


class Query:
    """One query's record: what was sent, and every event back."""

    def __init__(self, workload: str, method: str, seed: int,
                 warmup: bool):
        self.workload, self.method, self.seed = workload, method, seed
        self.warmup = warmup
        self.t_submit: Optional[float] = None
        self.t_admit: Optional[float] = None
        self.t_first_update: Optional[float] = None
        self.t_end: Optional[float] = None
        self.status = "missing"         # done | failed | missing
        self.events: List[Dict] = []    # update and done events

    @property
    def latency(self) -> Optional[float]:
        if self.status != "done":
            return None
        return self.t_end - self.t_submit

    @property
    def first_update(self) -> Optional[float]:
        if self.status != "done":
            return None
        t = self.t_first_update if self.t_first_update is not None \
            else self.t_end
        return t - self.t_submit


class ClosedLoop:
    """One client thread per stream pair.  Until the window opens each
    client sends warm-up queries from its warm-up stream; from the
    opening to the close, queries from its run stream; after the close
    none.  Queries in flight at the close run on and are drained by
    :meth:`drain`."""

    def __init__(self, submit: Callable, make_task: Callable,
                 warm_streams: Sequence, run_streams: Sequence):
        if len(warm_streams) != len(run_streams):
            raise ValueError("one warm-up and one run stream per client")
        self._submit = submit            # (task) -> event iterator
        self._make_task = make_task      # (Query, name) -> SearchTask
        self._warm = list(warm_streams)
        self._run = list(run_streams)
        self._lock = threading.Lock()
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.queries: List[Query] = []
        self.warm_done = [0] * len(self._run)
        self.warm_failed: List[Query] = []
        self._threads = [threading.Thread(target=self._client, args=(i,),
                                          name=f"bench-client-{i}",
                                          daemon=True)
                         for i in range(len(self._run))]

    def start(self) -> None:
        for t in self._threads:
            t.start()

    def open_window(self, seconds: float) -> None:
        with self._lock:
            self.t_open = time.perf_counter()
            self.t_close = self.t_open + seconds

    def _take(self, ci: int) -> Optional[Query]:
        with self._lock:
            if self.t_open is None:
                stream, warmup = self._warm[ci], True
            elif time.perf_counter() < self.t_close:
                stream, warmup = self._run[ci], False
            else:
                return None
            w, m, s = stream.next()
            q = Query(w, m, s, warmup=warmup)
            self.queries.append(q)
            return q

    def _client(self, ci: int) -> None:
        # the annotations label device-idle gaps in which the load
        # generator's own Python ran (bench/devtrace.py)
        from jax.profiler import TraceAnnotation
        n = 0
        while True:
            q = self._take(ci)
            if q is None:
                return
            with TraceAnnotation("bench.client.prepare"):
                task = self._make_task(q, f"c{ci}.{n}")
            n += 1
            q.t_submit = time.perf_counter()
            try:
                for ev in self._submit(task):
                    with TraceAnnotation("bench.client.record"):
                        if self._record(q, ev):
                            break
            except OSError as e:
                q.status = "failed"
                q.events.append({"event": "failed", "error": repr(e)})
            if q.warmup:
                with self._lock:
                    if q.status == "done":
                        self.warm_done[ci] += 1
                    else:
                        self.warm_failed.append(q)

    @staticmethod
    def _record(q: Query, ev: Dict) -> bool:
        """Keep one event of ``q``'s stream; True when it ends the query."""
        now = time.perf_counter()
        if "ok" in ev:
            q.t_admit = now
            if not ev["ok"]:
                q.status, q.t_end = "failed", now
                q.events.append(ev)
                return True
            return False
        kind = ev.get("event")
        ev["t_recv"] = now
        if kind == "update" and q.t_first_update is None:
            q.t_first_update = now
        if kind in ("update", "done"):
            q.events.append(ev)
        if kind in ("done", "failed"):
            q.status, q.t_end = kind, now
            return True
        return False

    def drain(self, deadline: float) -> bool:
        """Wait until every client has ended, or until the host-clock
        ``deadline``; True when all ended."""
        for t in self._threads:
            t.join(timeout=max(0.0, deadline - time.perf_counter()))
        return not any(t.is_alive() for t in self._threads)

    def warm_state(self):
        """(fewest warm-up queries a client has finished, the warm-up
        queries that failed)."""
        with self._lock:
            return min(self.warm_done), list(self.warm_failed)

    def window_queries(self) -> List[Query]:
        """Queries sent inside the window."""
        with self._lock:
            return [q for q in self.queries if not q.warmup]


def wait_until_warm(loop: ClosedLoop, meter: CompileMeter, queries: int,
                    quiet_s: float, limit_s: float) -> None:
    """Block until every client has finished ``queries`` warm-up queries
    and no program has been compiled or loaded for ``quiet_s`` seconds:
    the traffic then dispatches only programs it has already built.
    Raises when a warm-up query fails or ``limit_s`` passes first."""
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter()
        done, failed = loop.warm_state()
        if failed:
            raise RuntimeError(f"{len(failed)} warm-up queries failed: "
                               f"{failed[0].events[-1:]}")
        quiet = now - max(meter.last or t0, t0)
        if done >= queries and quiet >= quiet_s:
            return
        if now - t0 > limit_s:
            raise RuntimeError(
                f"warm-up not done in {limit_s} s: fewest queries done by "
                f"a client {done}, last compile {quiet:.1f} s ago")
        time.sleep(0.05)
