"""The one traffic generator: turns a traffic file's parameters, a
configuration's workload list and the run's seed into the queries the
clients send.

The load copies what a reproduction of the paper's Table III sends: the
program's own reproduction path (``repro.core.search.run_sweep``, and
``run_method_sweep`` for several methods) runs one search per workload
of the table, all at once, for each seed.  So the mix has
``clients_per_workload`` clients for each workload of the
configuration; each sends its workload again, with a fresh search seed,
when its last query is done.  Every run seed therefore holds the same
workloads and methods; the seed changes the search seeds and the order
in which each client takes the mix's methods.  Warm-up queries come
from streams of the same shape under a seed of their own that no run
uses.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: the warm-up's own seed; run seeds are independent draws from theirs
WARMUP_SEED = 0x5EED

Query = Tuple[str, str, int]          # (workload name, method, seed)


def _rng(seed: int, client: int) -> np.random.Generator:
    # any whole number, negative or past 64 bits, maps to a valid seed
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), int(client)])


class ClientStream:
    """The endless query stream of one client: its workload, the mix's
    methods in consecutive seeded permutations, a seeded search seed."""

    def __init__(self, workload: str, methods: Sequence[str], seed: int,
                 client: int):
        if not methods:
            raise ValueError("a traffic mix needs methods")
        self.workload = workload
        self._me = list(methods)
        self._rng = _rng(seed, client)
        self._mq: List[str] = []
        self._lock = threading.Lock()

    def next(self) -> Query:
        with self._lock:
            if not self._mq:
                self._mq = [self._me[i]
                            for i in self._rng.permutation(len(self._me))]
            seed = int(self._rng.integers(0, 2 ** 31 - 1))
            return self.workload, self._mq.pop(0), seed


def client_workloads(workloads: Sequence[str],
                     per_workload: int) -> List[str]:
    """The workload each client owns, client by client."""
    if not workloads:
        raise ValueError("a configuration needs workloads")
    return [w for w in workloads for _ in range(per_workload)]


def streams(workloads: Sequence[str], params: Dict,
            seed: int) -> List[ClientStream]:
    """One stream per client for a run seed."""
    return [ClientStream(w, params["methods"], seed, i)
            for i, w in enumerate(client_workloads(
                workloads, params["clients_per_workload"]))]


def traffic_params(traffic: Dict) -> Dict:
    """Validated parameters of a traffic file."""
    need = {"loop", "clients_per_workload", "methods", "drain_seconds",
            "warmup_queries_per_client", "warmup_quiet_seconds"}
    missing = need - set(traffic)
    if missing:
        raise ValueError(f"traffic file lacks {sorted(missing)}")
    if traffic["loop"] != "closed":
        raise ValueError(f"traffic loop {traffic['loop']!r}: only a "
                         f"closed loop is generated")
    if int(traffic["clients_per_workload"]) < 1:
        raise ValueError("a traffic mix needs at least one client per "
                         "workload")
    if not traffic["methods"]:
        raise ValueError("a traffic mix needs methods")
    return dict(clients_per_workload=int(traffic["clients_per_workload"]),
                methods=list(traffic["methods"]),
                drain_seconds=float(traffic["drain_seconds"]),
                warmup_queries=int(traffic["warmup_queries_per_client"]),
                warmup_quiet_s=float(traffic["warmup_quiet_seconds"]))
