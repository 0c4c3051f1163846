"""JAX batch evaluator for the SparseMap cost model, generalized over a
declared :class:`repro.core.arch.ArchSpec`.

A jit-compiled, vmap-vectorized re-implementation of
:mod:`repro.core.cost_model` that evaluates a whole *population* of genomes
in one XLA call.  The numpy implementation is the exact oracle; this one is
float32 and property-tested against it (tests/test_cost_agreement.py).

Compilation strategy: all workload- and platform-specific quantities
(primes, densities, tensor sizes, energy/capacity/fanout constants) are
*traced arguments*, and the prime list is padded to a bucket size — so a
single compilation is shared by every workload with the same
(ndims, bucket, topology) signature and every same-topology platform.
The arch's *structure* (loop-slot count, store tables, S/G site wiring,
NoC multicast/reduction shape, which parameters exist) is baked into the
kernel as closure constants; its *numbers* — including per-edge word
widths when any level departs from the global default — ride in the
traced parameter vector (``ArchSpec.param_vector``).  Per-tensor density
models follow the same split: the *mode* is structural — all-uniform
workloads bake the literal pre-density-model occupancy code
(bit-identical to the goldens) while any structured operand selects the
structured kernel variant — and within the structured variant the family
codes and numeric parameters (N:M's n/m, a band's coverage) are traced
rows, so a family of N:M workloads, or a whole mixed
uniform/banded/N:M fleet, shares ONE compilation.
``JaxCostModel.signature`` is therefore
``(ndims, prime_bucket, topology_fingerprint, density_key)``, and
``eval_stacked``/``MultiSearch`` mega-batching keeps sharing compilations
*within* a (topology, density-mode) pair.

The decode is fully tensorized: tiling factors via masked products over the
prime list, permutations via a (d!, d) lookup table, loop-nest reuse via
reverse cumulative products over the fixed n_levels*d loop-slot axis, and
the fiber-tree byte accounting via a lax.scan over the loop slots.
"""
from __future__ import annotations

import dataclasses
import threading
from functools import lru_cache
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from . import density as density_lib
from . import trace
from .accel import Platform
from .arch import ARCH_SPARSEMAP, ArchSpec, Topology, as_arch
from .encoding import GenomeSpec, all_permutations
from .es_ops import (DeviceSegment, PaddedLayout, SegmentResult,
                     segment_shape_key)
from .sparse import MAX_FMT_GENES
from .workload import WORD_BYTES

# Legacy constants: the default (paper) topology's store tables, kept for
# reference/backcompat.  The kernel derives its own per-topology tables.
GLB, PEBUF, REG = 0, 1, 2
STORE_OUTER = np.stack([
    np.isin(np.arange(ARCH_SPARSEMAP.n_levels),
            ARCH_SPARSEMAP.outer_levels_for[s])
    for s in ("glb", "pebuf", "reg")])
STORE_INNER = np.stack([
    np.isin(np.arange(ARCH_SPARSEMAP.n_levels),
            ARCH_SPARSEMAP.inner_levels_for[s])
    for s in ("glb", "pebuf", "reg")])
IS_SPATIAL_LEVEL = np.asarray(ARCH_SPARSEMAP.is_spatial)

# S/G lookup tables over gene value 0..6
_V = np.arange(7)
SG_LEADER_P = np.isin(_V, [2, 3, 5, 6])
SG_LEADER_Q = np.isin(_V, [1, 3, 4, 6])
SG_FOLLOW_P = np.isin(_V, [1, 3, 4, 6])
SG_FOLLOW_Q = np.isin(_V, [2, 3, 5, 6])
SG_IS_SKIP = _V >= 4
SG_IS_GATE = (_V >= 1) & (_V <= 3)

FMT_U, FMT_B, FMT_RLE, FMT_CP, FMT_UOP = range(5)


def _bucket(n: int, size: int = 16) -> int:
    return ((n + size - 1) // size) * size


# Registry of live jitted evaluators, keyed by compilation signature
# (ndims, padded prime count, topology fingerprint, density key, kind)
# where kind is "bcast" (workload constants broadcast over the batch) or
# "stacked" (each row's constants gathered from a per-task table, the
# mega-batch kernel) — used to count
# actual XLA compilations (one per distinct traced argument-shape set per
# signature).  The density key is "u" for all-uniform workloads (the
# literal pre-density-model kernel, bit-identical to the goldens) or
# "s:<registered families>" for the structured variant, in which the
# per-tensor family code and its numeric parameters are TRACED — a whole
# family of N:M workloads, or a mixed uniform/banded/N:M fleet, shares
# one compilation.
_JIT_FNS: Dict[Tuple[int, int, str, str, str], object] = {}

# One reentrant lock guards every module-level registry
# (_JIT_FNS/_SHARD_FNS/_STACK_TABLES/_AOT_*) and the reset baselines:
# the compile-ahead worker mutates them from its background thread while
# the search thread dispatches, so bare stores are not safe.
_LOCK = threading.RLock()

# The module's counters live in the trace recorder (``core/trace.py``),
# whose totals are cumulative since process start; each getter below
# reads the total minus its value at the getter's last reset.
_RESET_AT: Dict[str, float] = {}

#: recorder names of the counters and spans this module feeds
DISPATCHES = "fleet.dispatches"     # device dispatches
BLOCK = "fleet.block"               # host blocked on device->host copies
CA_HITS = "compile_ahead.hits"
CA_MISSES = "compile_ahead.misses"
CA_ERRORS = "compile_ahead.errors"
PREP_HITS = "fleet.stack_prep.hits"       # constants tables reused
PREP_MISSES = "fleet.stack_prep.misses"   # constants tables uploaded
TRANSFERS = "fleet.transfers"   # host<->device copies of the stacked path
ROWS = "fleet.rows"                 # genome rows sent to the device
ROWS_PADDED = "fleet.rows_padded"   # padding rows sent beside them
SCAN_TASKS = "fleet.scan_tasks"     # real tasks of a scan dispatch
SCAN_BUILDS = "fleet.scan_builds"   # scan programs built in the process

#: the names of the kernel programs, which XLA's module names carry
#: (``jit_<name>``) and device traces are read by
EVAL_PROGRAM = "eval_one"       # the row evaluator, broadcast or stacked
SCAN_PROGRAM = "one_task"       # the segment scans, canonical and direct


def _named(fn: Callable, name: str) -> Callable:
    fn.__name__ = name
    return fn


def _since_reset(name: str):
    with _LOCK:
        base = _RESET_AT.get(name, 0)
    return trace.total(name) - base


def _reset(*names: str) -> None:
    with _LOCK:
        for name in names:
            _RESET_AT[name] = trace.total(name)


def _count_dispatch() -> None:
    trace.count(DISPATCHES)


def _time_block(fn: Callable):
    """Run a blocking device->host conversion thunk inside a
    ``fleet.block`` span, whose seconds are :func:`host_blocked_s`."""
    with trace.span(BLOCK):
        return fn()


def host_blocked_s() -> float:
    """Seconds the host spent blocked on device->numpy conversions since
    the last reset."""
    return float(_since_reset(BLOCK))


def reset_host_blocked_s() -> None:
    _reset(BLOCK)


def compilation_count() -> int:
    """Total XLA compilations held by the shared evaluator cache: the sum
    of per-signature jit cache sizes (each distinct batch shape traced on
    a signature is one compilation), plus the AOT executables the
    compile-ahead worker built (shapes served from the AOT registry never
    enter a jit cache)."""
    total = 0
    with _LOCK:
        fns = list(_JIT_FNS.values())
        total += len(_AOT_FNS)
    for fn in fns:
        try:
            total += fn._cache_size()
        except Exception:       # private API; degrade to signature count
            total += 1
    return total


def compile_signatures() -> Tuple[Tuple[int, int, str, str], ...]:
    """The (ndims, prime-bucket, topology, density-key) signatures built
    so far."""
    with _LOCK:
        return tuple(sorted({(k[0], k[1], k[2], k[3]) for k in _JIT_FNS}))


def dispatch_count() -> int:
    """Device dispatches issued since the last reset (each batched
    evaluator call — per-task or mega-batch — is one dispatch)."""
    return int(_since_reset(DISPATCHES))


def reset_dispatch_count() -> None:
    _reset(DISPATCHES)


# ------------------------------------------------- AOT compile-ahead
#
# ``compile_ahead`` lowers and compiles predicted dispatch shapes on a
# background thread (jit(...).lower(shapes).compile()) while the host
# runs the HSHI/LHS prologue.  A ``.lower().compile()`` does NOT populate
# the jit function's own call cache, so the finished executables live in
# their own registry, keyed (jit-fn key, shape fingerprint), and the
# dispatch paths consult it first.  ``_AOT_PENDING`` holds an Event per
# in-flight background compile so a dispatch that races the worker WAITS
# for the executable instead of duplicate-tracing.

_AOT_FNS: Dict[Tuple, object] = {}
_AOT_PENDING: Dict[Tuple, threading.Event] = {}
_CA_PREFIXES: set = set()       # (sig..., tag) families the pass claims
# counted in the recorder: CA_HITS, dispatches served by an AOT
# executable; CA_MISSES, fresh XLA traces while compile-ahead is on;
# CA_ERRORS, failed background compiles / AOT calls
_CA_FIRST_ERROR: Optional[str] = None
_CA_CANCEL = None               # cancel event of the latest worker
_CA_THREAD: Optional[threading.Thread] = None   # the latest worker
_SCAN_BUILT: set = set()        # scan program keys built in the process


def compile_ahead_counts() -> Tuple[int, int]:
    """(hits, misses) of the AOT compile-ahead registry: a hit is a
    dispatch served by a pre-built executable, a miss a dispatch that had
    to trace a fresh XLA program even though compile-ahead ran."""
    return int(_since_reset(CA_HITS)), int(_since_reset(CA_MISSES))


def reset_compile_ahead_counts() -> None:
    global _CA_FIRST_ERROR
    with _LOCK:
        _reset(CA_HITS, CA_MISSES, CA_ERRORS)
        _CA_FIRST_ERROR = None


def compile_ahead_errors() -> Tuple[int, Optional[str]]:
    """(count, first error) of compile-ahead failures since the last
    reset: a background compile that raised (its dispatch then traces
    through ``jit`` and meets the same error in the open) or an AOT
    executable that raised when called (re-raised to the caller)."""
    with _LOCK:
        return int(_since_reset(CA_ERRORS)), _CA_FIRST_ERROR


def _record_ca_error(key: Tuple, exc: BaseException) -> None:
    global _CA_FIRST_ERROR
    trace.count(CA_ERRORS)
    with _LOCK:
        if _CA_FIRST_ERROR is None:
            _CA_FIRST_ERROR = f"{key}: {type(exc).__name__}: {exc}"


def _note_scan_build(key: Tuple, source: str) -> None:
    """Count the first build in the process of a scan program key in
    :data:`SCAN_BUILDS`: ``source`` is ``ahead`` (the compile-ahead
    worker) or ``inline`` (traced, compiled or loaded at its dispatch)."""
    with _LOCK:
        if key in _SCAN_BUILT:
            return
        _SCAN_BUILT.add(key)
    trace.count(SCAN_BUILDS, sig=key[:4], slots=key[5], source=source)


def _aot_lookup(key: Tuple):
    """The AOT executable for ``key``, waiting out an in-flight
    background compile of the same key first; None when absent."""
    with _LOCK:
        fn = _AOT_FNS.get(key)
        ev = _AOT_PENDING.get(key)
    if fn is not None or ev is None:
        return fn
    ev.wait(timeout=600.0)
    with _LOCK:
        return _AOT_FNS.get(key)


def _aot_call(key: Tuple, jit_fn, args: Tuple):
    """Dispatch through the AOT registry when it covers ``key``; else
    the ordinary jit call.  A compile-ahead MISS is a dispatch that had
    to trace a fresh XLA program even though compile-ahead claimed its
    (signature, kernel-tag) family — shapes in families the worker never
    touched (e.g. prologue probe batches when only scan/stacked shapes
    were predicted) don't count.  An AOT executable that raises is
    counted as a compile-ahead error and re-raised: its donated inputs
    may already be gone, so a second dispatch through jit is unsafe."""
    cfn = _aot_lookup(key)
    if cfn is not None:
        try:
            out = cfn(*args)
        except Exception as e:
            _record_ca_error(key, e)
            raise
        trace.count(CA_HITS)
        return out
    with _LOCK:
        armed = key[:5] in _CA_PREFIXES
    if not armed:
        return jit_fn(*args)
    try:
        before = jit_fn._cache_size()
    except Exception:
        before = None
    out = jit_fn(*args)
    try:
        traced = before is None or jit_fn._cache_size() > before
    except Exception:
        traced = True
    if traced:
        trace.count(CA_MISSES)
    return out


def compile_ahead(jobs: Sequence[Tuple[Tuple, object, Tuple]],
                  wait: bool = False) -> Optional[threading.Thread]:
    """Compile the given (key, jit_fn, arg_structs) jobs on a background
    thread.  Returns the thread (already started), or None when nothing
    was queued; ``wait=True`` joins it before returning (tests).  The
    jobs' (signature, kernel-tag) families become the claimed ones,
    which arms the miss counter on later dispatches in those families
    (``compile_ahead([])`` claims none).

    Every queued key is claimed in ``_AOT_PENDING`` *before* the worker
    starts: a dispatch that races the worker finds its key pending and
    waits for the executable (``_aot_lookup``) instead of tracing a
    duplicate program inline — a queued shape can never count as a miss,
    only a shape the predictor failed to enumerate.

    The worker is a NON-daemon thread with a cooperative cancel
    (:func:`compile_ahead_quiesce`): a daemon thread killed mid-XLA
    -compile at interpreter exit aborts the process from C++
    (``terminate called without an active exception``), so instead the
    fleet cancels leftover queue work when its run ends and interpreter
    shutdown joins at most the one in-flight compile.

    A new pass first retires the previous worker (cancel, then join: at
    most its in-flight compile).  Otherwise a key the previous, cancelled
    worker still held pending would be skipped here and then never
    compiled by it — its dispatch would trace inline as a miss."""
    global _CA_CANCEL, _CA_THREAD
    compile_ahead_quiesce()
    with _LOCK:
        prev = _CA_THREAD
    if prev is not None and prev is not threading.current_thread():
        prev.join()
    cancel = threading.Event()
    with _LOCK:
        # the families THIS pass claims: a fleet's misses are counted
        # against its own predictions, never an earlier fleet's
        _CA_PREFIXES.clear()
        _CA_PREFIXES.update(key[:5] for key, _, _ in jobs)
        queued = []
        for key, jit_fn, arg_structs in jobs:
            if key in _AOT_FNS or key in _AOT_PENDING:
                continue
            ev = threading.Event()
            _AOT_PENDING[key] = ev
            queued.append((key, jit_fn, arg_structs, ev))
        _CA_CANCEL = cancel
    if not queued:
        return None

    def work():
        # not the name it inherits from its creator (the sweep server's
        # worker), whose spans keep a profiler trace line of their own
        trace.name_os_thread("compile-ahead")
        for key, jit_fn, arg_structs, ev in queued:
            try:
                if not cancel.is_set():
                    compiled = jit_fn.lower(*arg_structs).compile()
                    with _LOCK:
                        _AOT_FNS[key] = compiled
                    if str(key[4]).startswith(("scan:", "dscan:")):
                        _note_scan_build(key, "ahead")
            except Exception as e:
                # counted, never silent: the dispatch of this key finds no
                # executable and traces through jit in the caller's thread
                _record_ca_error(key, e)
            finally:
                ev.set()
                with _LOCK:
                    _AOT_PENDING.pop(key, None)

    # daemon=False EXPLICITLY: daemon-ness is inherited from the creating
    # thread, and the sweep server runs fleets on a daemon worker — the
    # non-daemon guarantee above must not silently vanish there
    th = threading.Thread(target=work, name="compile-ahead", daemon=False)
    with _LOCK:
        _CA_THREAD = th
    th.start()
    if wait:
        th.join()
    return th


def compile_ahead_quiesce() -> None:
    """Cancel any compile-ahead work still queued (the in-flight compile
    finishes; skipped jobs release their pending events so no waiter
    hangs).  Called by the fleet when its run ends — whatever is still
    queued was predicted for dispatches that will never come — and at
    interpreter shutdown, so exit joins at most one in-flight compile."""
    with _LOCK:
        cancel = _CA_CANCEL
    if cancel is not None:
        cancel.set()


# threading._register_atexit callbacks fire BEFORE the interpreter joins
# non-daemon threads (plain atexit fires after, too late) — the same
# hook concurrent.futures uses to wind down its workers
try:
    threading._register_atexit(compile_ahead_quiesce)
except Exception:               # pragma: no cover - future-proofing
    import atexit
    atexit.register(compile_ahead_quiesce)


def clear_compile_cache() -> None:
    """Drop all shared jitted evaluators (benchmarking hook)."""
    _jitted_eval.cache_clear()
    _stacked_program.cache_clear()
    _stacked_fn.cache_clear()
    _build_eval_one.cache_clear()
    _scan_task_fn.cache_clear()
    _scan_fn.cache_clear()
    _direct_scan_task_fn.cache_clear()
    _direct_scan_fn.cache_clear()
    with _LOCK:
        _JIT_FNS.clear()
        _SHARD_FNS.clear()
        _STACK_TABLES.clear()
        _AOT_FNS.clear()
        _AOT_PENDING.clear()
        _CA_PREFIXES.clear()
        _SCAN_BUILT.clear()
    reset_stack_prep_counts()
    reset_dispatch_count()
    reset_compile_ahead_counts()
    reset_host_blocked_s()


# ------------------------------------------------------- topology tables


@dataclasses.dataclass(frozen=True)
class _TopoTables:
    """Structural constants the kernel builder derives from a Topology."""

    n_levels: int
    n_edges: int
    is_spatial: Tuple[bool, ...]            # per mapping level
    spatial_levels: Tuple[int, ...]
    store_outer: Tuple[Tuple[bool, ...], ...]   # (n_edges, n_levels)
    store_inner: Tuple[Tuple[bool, ...], ...]
    edge_site: Tuple[Optional[int], ...]    # per edge
    n_sites: int
    # param-vector layout (indices into the traced vector)
    fanout_idx: Tuple[int, ...]             # per spatial level
    cap_checks: Tuple[Tuple[int, int], ...]  # (edge idx, param idx)
    energy_idx: Tuple[Tuple[int, ...], ...]  # per edge: component indices
    bw_checks: Tuple[Tuple[int, int], ...]  # (edge idx, param idx)
    mac_idx: int
    # NoC scheme per edge (True/False/"frac") + the word-width
    # parameterization: with uniform_words the kernel bakes WORD_BYTES as
    # a constant (the pre-width code path); otherwise per-edge widths are
    # read from the param vector at word_idx, so same-topology
    # custom-width specs still share one compilation.  Fractional NoC
    # schemes read their discount fanout from the param-vector tail at
    # noc_mc_idx / noc_red_idx (None on all/none edges) — same split, so
    # a same-scheme family with different fanouts shares one compilation.
    noc_multicast: Tuple[Union[bool, str], ...] = ()
    noc_reduction: Tuple[Union[bool, str], ...] = ()
    uniform_words: bool = True
    word_idx: Tuple[int, ...] = ()          # per edge: param idx
    noc_mc_idx: Tuple[Optional[int], ...] = ()   # per edge: param idx|None
    noc_red_idx: Tuple[Optional[int], ...] = ()


@lru_cache(maxsize=32)
def _topo_tables(topo: Topology) -> _TopoTables:
    n_edges = len(topo.has_spatial)
    level_edge: List[int] = []
    is_spatial: List[bool] = []
    for e in range(n_edges):
        level_edge.append(e)
        is_spatial.append(False)
        if topo.has_spatial[e]:
            level_edge.append(e)
            is_spatial.append(True)
    nl = len(level_edge)
    spatial_levels = tuple(i for i, s in enumerate(is_spatial) if s)
    store_outer = tuple(
        tuple(level_edge[i] <= e for i in range(nl))
        for e in range(n_edges))
    store_inner = tuple(
        tuple(level_edge[i] > e for i in range(nl))
        for e in range(n_edges))

    # param vector layout mirrors ArchSpec.param_vector
    pos = 0
    fanout_idx = tuple(range(pos, pos + len(spatial_levels)))
    pos += len(spatial_levels)
    cap_checks = []
    for k in range(1, n_edges + 1):
        if topo.has_capacity[k]:
            cap_checks.append((k - 1, pos))
            pos += 1
    energy_idx = []
    for e in range(n_edges):
        energy_idx.append(tuple(range(pos, pos + topo.n_energy_comps[e])))
        pos += topo.n_energy_comps[e]
    bw_checks = []
    for e in range(n_edges):
        if topo.has_bandwidth[e]:
            bw_checks.append((e, pos))
            pos += 1
    mac_idx = pos
    word_idx = tuple(range(pos + 1, pos + 1 + n_edges))
    # fractional NoC fanouts trail the word widths (mirrors
    # ArchSpec.param_vector: edge order, multicast before reduction)
    noc_mc = topo.noc_multicast or (True,) * n_edges
    noc_red = topo.noc_reduction or (True,) * n_edges
    pos = word_idx[-1] + 1 if word_idx else mac_idx + 1
    noc_mc_idx: List[Optional[int]] = []
    noc_red_idx: List[Optional[int]] = []
    for e in range(n_edges):
        if noc_mc[e] == "frac":
            noc_mc_idx.append(pos)
            pos += 1
        else:
            noc_mc_idx.append(None)
        if noc_red[e] == "frac":
            noc_red_idx.append(pos)
            pos += 1
        else:
            noc_red_idx.append(None)

    return _TopoTables(
        n_levels=nl, n_edges=n_edges, is_spatial=tuple(is_spatial),
        spatial_levels=spatial_levels, store_outer=store_outer,
        store_inner=store_inner, edge_site=topo.edge_site,
        n_sites=len(topo.sg_sites), fanout_idx=fanout_idx,
        cap_checks=tuple(cap_checks), energy_idx=tuple(energy_idx),
        bw_checks=tuple(bw_checks), mac_idx=mac_idx,
        noc_multicast=noc_mc,
        noc_reduction=noc_red,
        uniform_words=topo.uniform_word_bytes,
        word_idx=word_idx,
        noc_mc_idx=tuple(noc_mc_idx), noc_red_idx=tuple(noc_red_idx))


# ------------------------------------------- density occupancy builders
#
# JAX counterparts of DensityModel.block_nonempty, keyed by family name.
# Each takes (params_row, elems) where params_row is the traced
# [code, hit_rate, family params...] row (density.param_row) and elems
# the (possibly fractional) tile extents, and returns P(block nonempty).
# Custom families register with :func:`register_density_occ` BEFORE
# building evaluators (the structured kernel bakes the registered set at
# trace time; the registry fingerprint is part of the signature).


def _occ_uniform(pr, e):
    return 1.0 - jnp.power(1.0 - pr[2], jnp.maximum(e, 1.0))


def _occ_banded(pr, e):
    cov = jnp.maximum(pr[3], 1e-30)
    d_in = jnp.clip(pr[2] / cov, 0.0, 1.0)
    return cov * (1.0 - jnp.power(1.0 - d_in, jnp.maximum(e, 1.0)))


def _occ_block_nm(pr, e):
    # hypergeometric miss: C(m-n, e) / C(m, e) via log-gamma (fractional
    # e supported); any window wider than the zero budget m-n must hit
    from jax.scipy.special import gammaln
    n_, m_ = pr[2], pr[3]
    free = m_ - n_
    e_ = jnp.maximum(e, 1.0)
    ec = jnp.minimum(e_, free)
    lg = (gammaln(free + 1.0) + gammaln(m_ - ec + 1.0)
          - gammaln(free - ec + 1.0) - gammaln(m_ + 1.0))
    return jnp.where(e_ > free, 1.0, 1.0 - jnp.exp(lg))


_JAX_OCC = {"uniform": _occ_uniform, "banded": _occ_banded,
            "block_nm": _occ_block_nm}


def register_density_occ(family: str, fn) -> None:
    """Register the JAX occupancy builder of a custom density family
    (numpy side: ``density.register_density_model``).  Must happen before
    any structured evaluator is built."""
    if family in _JAX_OCC and _JAX_OCC[family] is not fn:
        raise ValueError(f"density family {family!r} already has a JAX "
                         f"occupancy builder")
    _JAX_OCC[family] = fn


def _occ_structured(pr, e):
    """Trace-time dispatch over the registered families: every family's
    occupancy is computed and the traced per-tensor code selects one —
    the family assignment rides in the traced params, so it never splits
    compilations."""
    fams = density_lib.registered_families()
    missing = [f for f in fams if f not in _JAX_OCC]
    if missing:
        raise KeyError(
            f"density families {missing} have no JAX occupancy builder; "
            f"call jax_cost.register_density_occ (COMPAT.md)")
    out = _JAX_OCC[fams[0]](pr, e)
    for fam in fams[1:]:
        out = jnp.where(pr[0] == float(density_lib.family_code(fam)),
                        _JAX_OCC[fam](pr, e), out)
    return out


# ---------------------------------------------------------------- kernel


def _clog2(x):
    """Metadata bits per index: ceil(log2 x), at least 1 — the kernel
    twin of the oracle's ``sparse._clog2``.  Computed exactly from the
    float32 exponent (x = m * 2^e with m in [0.5, 1), so ceil(log2 x) is
    e - 1 when m == 0.5 and e otherwise): ``ceil(log2(x))`` is off by a
    whole bit on the TPU, whose float32 log2 overshoots exact powers of
    two (2^13 first), and on every backend just above large ones."""
    m, e = jnp.frexp(jnp.maximum(x, 2.0))
    bits = jnp.where(m == 0.5, e - 1, e).astype(jnp.float32)
    return jnp.maximum(1.0, bits)


def _varying_like(init, ref):
    """Cast constant ``lax.scan`` carry inits to the varying manual mesh
    axes of ``ref``.  Inside ``shard_map`` (which checks varying manual
    axes) a carry that starts as a literal but is updated from sharded
    inputs changes type across the body; outside it ``ref`` varies over
    no axis and the inits pass through unchanged."""
    axes = tuple(jax.typeof(ref).vma)
    if not axes:
        return init
    return jax.tree.map(lambda x: jax.lax.pcast(x, axes, to="varying"),
                        init)


@lru_cache(maxsize=64)
def _build_eval_one(d: int, n_primes_pad: int, topo: Topology,
                    dens_key: str = "u"):
    """Build the un-vmapped per-row kernel closure for (ndims=d, padded
    prime count, topology, density mode).  Every dispatch path — the
    broadcast and stacked batch evaluators, the sharded mega-batch, and
    the device-resident ``run_segments`` scan — vmaps this ONE closure,
    so per-row results are identical across all of them.

    ``dens_key == "u"`` bakes the uniform-random occupancy model exactly
    as the pre-density-model code did (bit-identical to the goldens);
    any other value builds the structured variant, in which each
    tensor's density-model family code and numeric parameters are read
    from the traced ``dens_params`` rows (see ``_occ_structured``)."""
    tt = _topo_tables(topo)
    structured = dens_key != "u"
    NL = tt.n_levels
    NE = tt.n_edges
    perm_table = jnp.asarray(all_permutations(d), jnp.int32)
    store_outer_lv = jnp.asarray(np.asarray(tt.store_outer))  # (NE, NL)
    store_inner_lv = jnp.asarray(np.asarray(tt.store_inner))
    spatial_lv = jnp.asarray(np.asarray(tt.is_spatial))
    lvl_of = jnp.repeat(jnp.arange(NL), d)          # (nl,)
    wb = float(WORD_BYTES)

    def eval_one(perm_genes, assign, fmt_genes, sg,
                 primes, prime_dim, relevance, densities, full_elems,
                 total_macs, z_onehot, plat, dens_params):
        # ---- tiling factors (NL, d) ----
        lvl_eq = assign[None, :] == jnp.arange(NL,
                                               dtype=jnp.int32)[:, None]
        dim_eq = prime_dim[None, :] == jnp.arange(d, dtype=jnp.int32)[:, None]
        mask = lvl_eq[:, None, :] & dim_eq[None, :, :]     # (NL, d, np)
        factors = jnp.prod(jnp.where(mask, primes[None, None, :], 1.0),
                           axis=-1)                        # (NL, d) float32

        # ---- flattened loops ----
        loop_dims = perm_table[perm_genes]                 # (NL, d)
        dims_flat = loop_dims.reshape(-1)                  # (nl,)
        bounds = factors[lvl_of, dims_flat]
        spatial_flat = spatial_lv[lvl_of]

        fanouts = [jnp.prod(factors[lvl]) for lvl in tt.spatial_levels]
        rel_flat = relevance[:, dims_flat]                 # (3, nl)
        transparent = bounds <= 1.0

        store_outer = store_outer_lv[:, lvl_of]            # (NE, nl)

        def fills_for(s, t):
            active = store_outer[s]
            irrel = ~rel_flat[t]
            passthru = jnp.where(active, irrel | transparent, True)
            in_suffix = jnp.flip(jnp.cumprod(
                jnp.flip(passthru.astype(jnp.float32)))) > 0.5
            contrib = jnp.where(rel_flat[t], bounds,
                                jnp.where(~spatial_flat, bounds, 1.0))
            mult = jnp.prod(jnp.where(active & ~in_suffix, contrib, 1.0))
            # NoC scheme of edge s: without multicast (reads) /
            # in-network reduction (the output, tensor 2), every spatial
            # instance's copy crosses the edge — irrelevant spatial loops
            # multiply traffic wherever they sit in the nest (suffix
            # included).  Fractional schemes carry max(S / fanout, 1)
            # copies over the same loop set, the fanout traced from the
            # param-vector tail (same-scheme families share compilation).
            scheme = (tt.noc_reduction[s] if t == 2
                      else tt.noc_multicast[s])
            if scheme == "frac":
                fi = tt.noc_red_idx[s] if t == 2 else tt.noc_mc_idx[s]
                s_irrel = jnp.prod(jnp.where(
                    active & irrel & spatial_flat, bounds, 1.0))
                mult = mult * jnp.maximum(s_irrel / plat[fi], 1.0)
            elif not scheme:
                mult = mult * jnp.prod(jnp.where(
                    active & irrel & spatial_flat, bounds, 1.0))
            tile = jnp.prod(jnp.where(
                store_inner_lv[s][:, None] & relevance[t][None, :],
                factors, 1.0))
            return tile * mult

        fills = jnp.stack([jnp.stack([fills_for(s, t) for t in range(3)])
                           for s in range(NE)])            # (NE, 3)

        # ---- fiber-tree format accounting per tensor ----
        def tensor_format(t):
            genes = fmt_genes[t]
            is_sub = rel_flat[t] & (bounds > 1.0)
            k = jnp.sum(is_sub.astype(jnp.int32))
            rank = jnp.cumsum(is_sub.astype(jnp.int32)) - 1
            gidx = rank + jnp.maximum(MAX_FMT_GENES - k, 0)
            fmt = jnp.where(is_sub & (gidx < MAX_FMT_GENES) & (gidx >= 0),
                            genes[jnp.clip(gidx, 0, MAX_FMT_GENES - 1)],
                            FMT_U)
            dens = densities[t]
            sub_bounds = jnp.where(is_sub, bounds, 1.0)
            suffix_prod = jnp.flip(jnp.cumprod(jnp.flip(sub_bounds)))
            elems_below = suffix_prod / sub_bounds
            if structured:
                occ = _occ_structured(dens_params[t], elems_below)
            else:
                # all-uniform: the literal pre-density-model expression
                occ = 1.0 - jnp.power(1.0 - dens,
                                      jnp.maximum(elems_below, 1.0))
            kept = sub_bounds * occ
            full = full_elems[t]

            def body(carry, xs):
                n_fibers, meta_bits = carry
                L, f, kp, sub = xs
                mb = jnp.select(
                    [f == FMT_B, f == FMT_RLE, f == FMT_CP, f == FMT_UOP],
                    [n_fibers * L,
                     n_fibers * kp * _clog2(L),
                     n_fibers * kp * _clog2(L),
                     n_fibers * (L + 1.0) * _clog2(jnp.maximum(full, 2.0))],
                    0.0)
                meta_bits = meta_bits + jnp.where(sub > 0.5, mb, 0.0)
                nf_next = jnp.where(f == FMT_U, n_fibers * L, n_fibers * kp)
                n_fibers = jnp.where(sub > 0.5, nf_next, n_fibers)
                return (n_fibers, meta_bits), None

            (_, meta_bits), _ = jax.lax.scan(
                body, _varying_like((jnp.float32(1.0), jnp.float32(0.0)),
                                    full),
                (sub_bounds, fmt, kept, is_sub.astype(jnp.float32)))
            compressed = jnp.any(jnp.where(is_sub, fmt != FMT_U, False))
            data_b = jnp.where(compressed, full * dens * wb, full * wb)
            ratio = (data_b + meta_bits / 8.0) / jnp.maximum(full * wb, 1.0)

            comp_here = jnp.where(is_sub, (fmt != FMT_U).astype(jnp.float32),
                                  0.0)
            comp_after = jnp.flip(jnp.cumsum(jnp.flip(comp_here))) - comp_here
            uop_bad = jnp.any(is_sub & (fmt == FMT_UOP) & (comp_after < 0.5))
            spat_bad = jnp.any(is_sub & spatial_flat & (fmt != FMT_U))
            return ratio, compressed, uop_bad | spat_bad, meta_bits

        rs, comps, bads, metas = zip(*[tensor_format(t) for t in range(3)])
        ratios = jnp.stack(rs)
        fmt_invalid = bads[0] | bads[1] | bads[2]
        p_comp, q_comp = comps[0], comps[1]

        # ---- S/G (sg has one gene per site; compute site "C" last) ----
        lead_p = jnp.asarray(SG_LEADER_P)[sg]
        lead_q = jnp.asarray(SG_LEADER_Q)[sg]
        fol_p = jnp.asarray(SG_FOLLOW_P)[sg]
        fol_q = jnp.asarray(SG_FOLLOW_Q)[sg]
        skips = jnp.asarray(SG_IS_SKIP)[sg]
        gates = jnp.asarray(SG_IS_GATE)[sg]
        if structured:
            # element-granularity intersection hit rates of the input
            # leaders (DensityModel.hit_rate, traced per tensor)
            d_p, d_q = dens_params[0, 1], dens_params[1, 1]
        else:
            d_p, d_q = densities[0], densities[1]
        sg_invalid = jnp.any(skips & ((lead_p & ~p_comp) |
                                      (lead_q & ~q_comp)))
        frac_e_p = jnp.where(fol_p & (skips | gates), d_q, 1.0)
        frac_e_q = jnp.where(fol_q & (skips | gates), d_p, 1.0)
        frac_t_p = jnp.where(fol_p & skips, d_q, 1.0)
        frac_t_q = jnp.where(fol_q & skips, d_p, 1.0)
        cyc_frac = jnp.where(jnp.any(skips & lead_p), d_p, 1.0) * \
            jnp.where(jnp.any(skips & lead_q), d_q, 1.0)
        e_frac = jnp.where(jnp.any((skips | gates) & lead_p), d_p, 1.0) * \
            jnp.where(jnp.any((skips | gates) & lead_q), d_q, 1.0)

        # ---- traffic ----
        total_z = jnp.sum(full_elems * z_onehot)
        is_z = z_onehot                                     # (3,)
        one = jnp.float32(1.0)
        fe_rows, ft_rows = [], []
        for e in range(NE):
            si = tt.edge_site[e]
            if si is None:
                fe_rows.append(jnp.stack([one, one, one]))
                ft_rows.append(jnp.stack([one, one, one]))
            else:
                fe_rows.append(jnp.stack([frac_e_p[si], frac_e_q[si], one]))
                ft_rows.append(jnp.stack([frac_t_p[si], frac_t_q[si], one]))
        fe = jnp.stack(fe_rows)                             # (NE, 3)
        ft = jnp.stack(ft_rows)
        f_rmw = jnp.maximum(2.0 * fills - total_z, total_z)
        fills_adj = jnp.where(is_z[None, :] > 0.5, f_rmw, fills)

        def _tile_elems(s):
            return jnp.stack([
                jnp.prod(jnp.where(
                    store_inner_lv[s][:, None] & relevance[t][None, :],
                    factors, 1.0)) for t in range(3)])

        if tt.uniform_words:
            # default-width topology: the pre-word-width code, the global
            # width baked as a constant (bit-identical to the goldens)
            byt = fills_adj * wb * ratios[None, :]          # (NE edges, 3 t)

            def tile_bytes(s):
                return jnp.sum(_tile_elems(s) * wb * ratios)
        else:
            # per-edge widths from the param vector: data bytes scale
            # with the width, metadata bits do not, so the compression
            # ratio is recomputed per edge (edge s fills store s+1, whose
            # width also prices that store's occupancy)
            wbs = jnp.stack([plat[i] for i in tt.word_idx])  # (NE,)
            full_wb = full_elems[None, :] * wbs[:, None]     # (NE, 3)
            data_b = jnp.where(
                jnp.stack(comps)[None, :],
                full_elems[None, :] * densities[None, :] * wbs[:, None],
                full_wb)
            ratios_e = (data_b + jnp.stack(metas)[None, :] / 8.0) / \
                jnp.maximum(full_wb, 1.0)                    # (NE, 3)
            byt = fills_adj * wbs[:, None] * ratios_e

            def tile_bytes(s):
                return jnp.sum(_tile_elems(s) * wbs[s] * ratios_e[s])
        tr_e = byt * fe
        tr_t = byt * ft

        # ---- validity, energy, latency (param-vector driven) ----
        invalid = jnp.bool_(False)
        for fan, pi in zip(fanouts, tt.fanout_idx):
            invalid = invalid | (fan > plat[pi])
        invalid = invalid | fmt_invalid | sg_invalid
        for e, pi in tt.cap_checks:
            invalid = invalid | (tile_bytes(e) > plat[pi])

        # left-associated sums/products, matching the legacy kernel's
        # float32 evaluation order exactly
        edge_energies = []
        for e in range(NE):
            comps_e = [plat[i] for i in tt.energy_idx[e]]
            e_edge = comps_e[0]
            for c in comps_e[1:]:
                e_edge = e_edge + c
            edge_energies.append(jnp.sum(tr_e[e]) * e_edge)
        energy = edge_energies[0]
        for term in edge_energies[1:]:
            energy = energy + term
        energy = energy + total_macs * e_frac * plat[tt.mac_idx]
        fan_prod = fanouts[0] if fanouts else one
        for fan in fanouts[1:]:
            fan_prod = fan_prod * fan
        compute_cycles = (total_macs / fan_prod) * cyc_frac
        cycles = compute_cycles
        for e, pi in tt.bw_checks:
            cycles = jnp.maximum(cycles, jnp.sum(tr_t[e]) / plat[pi])
        edp = cycles * energy
        log10_edp = jnp.log10(jnp.maximum(cycles, 1e-30)) + \
            jnp.log10(jnp.maximum(energy, 1e-30))
        valid = ~invalid
        big = jnp.float32(jnp.inf)
        return dict(valid=valid,
                    energy_pj=jnp.where(valid, energy, big),
                    cycles=jnp.where(valid, cycles, big),
                    edp=jnp.where(valid, edp, big),
                    log10_edp=jnp.where(valid, log10_edp, big))

    return _named(eval_one, EVAL_PROGRAM)


@lru_cache(maxsize=32)
def _jitted_eval(d: int, n_primes_pad: int, topo: Topology,
                 dens_key: str = "u"):
    """The jitted batch evaluator for (ndims=d, padded prime count,
    topology, density mode): :func:`_build_eval_one` vmapped over the
    batch axis, the workload/platform quantities broadcast over the batch
    (one workload per call)."""
    eval_one = _build_eval_one(d, n_primes_pad, topo, dens_key)
    fn = jax.jit(jax.vmap(eval_one, in_axes=(0, 0, 0, 0) + (None,) * 9))
    with _LOCK:
        _JIT_FNS[(d, n_primes_pad, topo.fingerprint, dens_key,
                  "bcast")] = fn
    return fn


def _split_rows(rows, nl: int, n_pad: int) -> Tuple:
    """The kernel's (perm, tiling, fmt, sg) inputs as column slices of a
    packed (B, W) int32 row buffer (:meth:`JaxCostModel._pack`), numpy or
    jax alike; the last column, the row's task index, is left out."""
    f = nl + n_pad
    s = f + 3 * MAX_FMT_GENES
    return (rows[:, :nl], rows[:, nl:f],
            rows[:, f:s].reshape(-1, 3, MAX_FMT_GENES), rows[:, s:-1])


def _unpack_consts(rows, layout: Tuple) -> List:
    """The nine per-row kernel constants from the float32 rows of a
    per-task table (``JaxCostModel._const_row``): each (shape, dtype) of
    ``layout`` in turn, cast back exactly (small integers and 0/1 flags
    are exact in float32)."""
    out, off = [], 0
    for shape, dtype in layout:
        size = int(np.prod(shape))
        out.append(rows[:, off:off + size].reshape((-1,) + shape)
                   .astype(np.dtype(dtype)))
        off += size
    return out


@lru_cache(maxsize=32)
def _stacked_program(d: int, n_pad: int, topo: Topology, dens_key: str,
                     layout: Tuple) -> Callable:
    """The un-jitted mega-batch program: ``(rows, table) -> (3, B)``.
    ``rows`` is the packed int32 row buffer, its last column each row's
    index into ``table``, the (slots, width) float32 per-task constants
    table; each row gathers its constants and runs the same vmapped
    per-row kernel as the broadcast path.  The result rows are ``valid``
    (0/1), ``energy_pj`` and ``cycles``; ``edp`` and ``log10_edp`` are
    derived on the host (:func:`_canonical`)."""
    veval = jax.vmap(_build_eval_one(d, n_pad, topo, dens_key))
    nl = _topo_tables(topo).n_levels

    def program(rows, table):
        consts = _unpack_consts(table[rows[:, -1]], layout)
        out = veval(*_split_rows(rows, nl, n_pad), *consts)
        return jnp.stack([out["valid"].astype(jnp.float32),
                          out["energy_pj"], out["cycles"]])

    return _named(program, EVAL_PROGRAM)


@lru_cache(maxsize=32)
def _stacked_fn(d: int, n_pad: int, topo: Topology, dens_key: str,
                layout: Tuple):
    """The jitted :func:`_stacked_program` (``eval_stacked``'s kernel)."""
    fn = jax.jit(_stacked_program(d, n_pad, topo, dens_key, layout))
    with _LOCK:
        _JIT_FNS[(d, n_pad, topo.fingerprint, dens_key, "stacked")] = fn
    return fn


# -------------------------------------------------- device-resident scan

# Mesh-sharded jitted variants, keyed by (signature..., kind, mesh key).
# Kept out of the lru_caches because a Mesh is identified by its device
# set + axis names, not object identity.
_SHARD_FNS: Dict[Tuple, object] = {}


def _mesh_key(mesh) -> Tuple:
    devs = np.asarray(mesh.devices).reshape(-1)
    return (tuple(mesh.axis_names), tuple(int(d.id) for d in devs))


def _mesh_ndev(mesh) -> int:
    return 1 if mesh is None else int(np.asarray(mesh.devices).size)


@lru_cache(maxsize=32)
def _scan_task_fn(d: int, n_pad: int, topo: Topology, dens_key: str,
                  n_parents: int, n_elite: int, genes_per: int,
                  restart: int = 0):
    """The un-jitted scan program for ONE fleet of same-shape tasks:
    vmap over the task axis of a ``lax.scan`` over generations, each
    step folding {stable-sort elitist selection -> crossover -> mutation
    -> clip/fixed-genes -> batched cost eval} into the carry.

    All randomness arrives pre-drawn in the ``draws`` xs (plan arrays in
    PADDED genome coordinates — see ``es_ops.PaddedLayout``), so the
    program is a pure function of its inputs; the carry fitness for
    selection is the explicit ``cycles * energy`` product of the emitted
    outputs, the same multiply ``_canonical`` performs on the host.

    ``restart > 0`` extends the carry with the float32 best-so-far and a
    no-improvement counter: after ``restart`` stagnant generations the
    non-elite population is replaced by the pre-drawn fresh block of
    ``draws["fresh"]`` (always evaluated — fixed shapes — and adopted
    via a where-select on the carry, the ``lax.cond`` re-init branch in
    its vmap-compatible form).  ``restart == 0`` builds EXACTLY the
    pre-restart program."""
    eval_one = _build_eval_one(d, n_pad, topo, dens_key)
    tt = _topo_tables(topo)
    NL = tt.n_levels
    F3 = 3 * MAX_FMT_GENES
    veval = jax.vmap(eval_one, in_axes=(0, 0, 0, 0) + (None,) * 9)

    def eval_rows(kids, consts):
        C = kids.shape[0]
        perm = kids[:, :NL]
        til = kids[:, NL:NL + n_pad]
        fmt = kids[:, NL + n_pad:NL + n_pad + F3].reshape(
            C, 3, MAX_FMT_GENES)
        sg = kids[:, NL + n_pad + F3:]
        return veval(perm, til, fmt, sg, *consts)

    def one_task(pop, edp, gene_ub, fixed_mask, fixed_vals, draws, consts):
        def make_kids(pop, order, dr):
            parents = pop[order[:n_parents]]
            Lp = pop.shape[1]
            col = jnp.arange(Lp)[None, :]
            kids = jnp.where(col < dr["cuts"][:, None],
                             parents[dr["ab"][:, 0]],
                             parents[dr["ab"][:, 1]])
            C = kids.shape[0]
            rows = jnp.arange(C)
            # draw-order duplicate overwrite: one column at a time (row
            # indices are unique per column, so the order is defined)
            for j in range(genes_per):
                g = dr["gene"][:, j]
                kids = kids.at[rows, g].set(
                    jnp.where(dr["active"], dr["vals"][:, j],
                              kids[rows, g]))
            kids = jnp.clip(kids, 0, gene_ub[None, :] - 1)
            kids = jnp.where(fixed_mask[None, :], fixed_vals[None, :],
                             kids)
            return kids

        def step(carry, dr):
            pop, edp = carry
            order = jnp.argsort(edp)            # stable sort
            elites = pop[order[:n_elite]]
            elite_edp = edp[order[:n_elite]]
            kids = make_kids(pop, order, dr)
            out = eval_rows(kids, consts)
            kedp = out["cycles"] * out["energy_pj"]
            new_pop = jnp.concatenate([elites, kids], axis=0)
            new_edp = jnp.concatenate([elite_edp, kedp], axis=0)
            ys = dict(kids=kids, valid=out["valid"],
                      energy_pj=out["energy_pj"], cycles=out["cycles"])
            return (new_pop, new_edp), ys

        def step_restart(carry, dr):
            pop, edp, best, since = carry
            order = jnp.argsort(edp)
            elites = pop[order[:n_elite]]
            elite_edp = edp[order[:n_elite]]
            kids = make_kids(pop, order, dr)
            out = eval_rows(kids, consts)
            kedp = out["cycles"] * out["energy_pj"]
            kbest = jnp.minimum(best, jnp.min(kedp))
            since1 = jnp.where(kbest < best, 0, since + 1)
            # the fresh block is always evaluated (fixed shapes) and only
            # ADOPTED when the stagnation threshold trips
            fresh = dr["fresh"]
            fout = eval_rows(fresh, consts)
            fedp = fout["cycles"] * fout["energy_pj"]
            do_r = since1 >= restart
            new_pop = jnp.where(
                do_r, jnp.concatenate([elites, fresh], axis=0),
                jnp.concatenate([elites, kids], axis=0))
            new_edp = jnp.where(
                do_r, jnp.concatenate([elite_edp, fedp], axis=0),
                jnp.concatenate([elite_edp, kedp], axis=0))
            best2 = jnp.where(do_r, jnp.minimum(kbest, jnp.min(fedp)),
                              kbest)
            since2 = jnp.where(do_r, 0, since1)
            ys = dict(kids=kids, valid=out["valid"],
                      energy_pj=out["energy_pj"], cycles=out["cycles"],
                      f_valid=fout["valid"],
                      f_energy_pj=fout["energy_pj"],
                      f_cycles=fout["cycles"], restarted=do_r)
            return (new_pop, new_edp, best2, since2), ys

        if restart > 0:
            best0 = draws["best0"][0]
            since0 = draws["since0"][0]
            dr_xs = {kk: v for kk, v in draws.items()
                     if kk not in ("best0", "since0")}
            (pop, edp, best, since), ys = jax.lax.scan(
                step_restart, (pop, edp, best0, since0), dr_xs)
            ys = dict(ys, best=best[None], since=since[None])
            return pop, edp, ys
        (pop, edp), ys = jax.lax.scan(step, (pop, edp), draws)
        return pop, edp, ys

    return jax.vmap(_named(one_task, SCAN_PROGRAM),
                    in_axes=(0, 0, 0, 0, 0, 0, 0))


def _per_task_carry(vfn: Callable) -> Callable:
    """The scan program as dispatched: the carry (pop, edp) enters and
    leaves as one array per task, stacked and split inside the program.
    Stacking or slicing it outside would run eager XLA ops, whose
    programs compile anew for every task count and task index.  The
    stacked final (pop, edp) comes back too: a pipelined driver reads it
    after the next dispatch has taken (donated) the per-task carries."""
    def program(pops, edps, *rest):
        pop_f, edp_f, ys = vfn(jnp.stack(pops), jnp.stack(edps), *rest)
        n = len(pops)
        return (pop_f, edp_f, ys, tuple(pop_f[i] for i in range(n)),
                tuple(edp_f[i] for i in range(n)))
    return _named(program, SCAN_PROGRAM)


def _donate_args() -> Tuple[int, ...]:
    """Donate the scan carry buffers (pop, edp) on accelerators so a
    pipelined fleet's device-resident populations update in place;
    donation on CPU only produces warnings, so it stays gated."""
    return (0, 1) if jax.default_backend() in ("gpu", "tpu") else ()


@lru_cache(maxsize=32)
def _scan_fn(d: int, n_pad: int, topo: Topology, dens_key: str,
             n_parents: int, n_elite: int, genes_per: int,
             restart: int = 0):
    fn = jax.jit(_per_task_carry(_scan_task_fn(
        d, n_pad, topo, dens_key, n_parents, n_elite, genes_per, restart)),
                 donate_argnums=_donate_args())
    tag = f"scan:p{n_parents}e{n_elite}g{genes_per}" + (
        f"r{restart}" if restart else "")
    with _LOCK:
        _JIT_FNS[(d, n_pad, topo.fingerprint, dens_key, tag)] = fn
    return fn


@lru_cache(maxsize=32)
def _direct_scan_task_fn(d: int, n_pad: int, topo: Topology,
                         dens_key: str, n_parents: int, n_elite: int,
                         genes_per: int):
    """The scan program for ``standard_es`` segments: the same
    {select -> single-point crossover -> gated mutation -> cost} fold,
    but the carry population lives in DIRECT value coordinates
    (``direct_encoding.DirectValueSpec`` layout: [perm codes | factor
    values d x n_levels | fmt/sg tail]) and every generation's children
    are translated to canonical rows IN-SCAN — the jnp twin of
    ``DirectValueSpec.to_canonical``'s greedy prime placement, vectorized
    over rows and unrolled over the padded prime axis with the prime
    value/dimension TRACED (from the shared consts), so same-signature
    workloads share one compilation.  The scrambled permutation table and
    dim sizes are traced per-task aux inputs for the same reason.

    Numerics note: factor products and remainders stay well inside
    float32's exact-integer range, so the divisibility/validity decisions
    are exact — the translation equals the numpy oracle row-for-row
    (test-pinned)."""
    eval_one = _build_eval_one(d, n_pad, topo, dens_key)
    tt = _topo_tables(topo)
    NL = tt.n_levels
    F3 = 3 * MAX_FMT_GENES
    tail_len = F3 + tt.n_sites
    Ld = NL + d * NL + tail_len
    veval = jax.vmap(eval_one, in_axes=(0, 0, 0, 0) + (None,) * 9)

    def one_task(pop, edp, scramble, dim_sizes, draws, consts):
        primes_f, prime_dim = consts[0], consts[1]

        def translate(kids):
            C = kids.shape[0]
            perm = scramble[kids[:, :NL]].astype(jnp.int32)
            factors = kids[:, NL:NL + d * NL].reshape(
                C, d, NL).astype(jnp.float32)
            prod = jnp.prod(factors, axis=2)                # (C, d)
            ok = jnp.all(prod == dim_sizes[None, :], axis=1)
            remaining = factors
            til = jnp.zeros((C, n_pad), dtype=jnp.int32)
            for kk in range(n_pad):
                p = primes_f[kk]
                di = prime_dim[kk]
                is_real = p > 1.5       # pad primes are 1.0
                rem = jax.lax.dynamic_index_in_dim(
                    remaining, di, axis=1, keepdims=False)  # (C, NL)
                can = (jnp.mod(rem, p) == 0) & (rem > 1.0)
                lvl = jnp.argmax(can, axis=1).astype(jnp.int32)
                hasl = jnp.any(can, axis=1)
                ok = ok & (hasl | ~is_real)
                upd = ((jnp.arange(NL)[None, :] == lvl[:, None]) &
                       hasl[:, None] & is_real)
                remaining = jax.lax.dynamic_update_index_in_dim(
                    remaining, jnp.where(upd, rem / p, rem), di, axis=1)
                til = til.at[:, kk].set(
                    jnp.where(is_real & hasl, lvl, 0))
            return perm, til, ok

        def step(carry, dr):
            pop, edp = carry
            order = jnp.argsort(edp)            # stable sort
            parents = pop[order[:n_parents]]
            elites = pop[order[:n_elite]]
            elite_edp = edp[order[:n_elite]]
            col = jnp.arange(Ld)[None, :]
            kids = jnp.where(col < dr["cuts"][:, None],
                             parents[dr["ab"][:, 0]],
                             parents[dr["ab"][:, 1]])
            C = kids.shape[0]
            rows = jnp.arange(C)
            for j in range(genes_per):
                g = dr["gene"][:, j]
                kids = kids.at[rows, g].set(
                    jnp.where(dr["active"], dr["vals"][:, j],
                              kids[rows, g]))
            # direct mutation draws are valid values by construction —
            # no clip, no fixed genes (matches the host loop exactly)
            perm, til, ok = translate(kids)
            tail = kids[:, NL + d * NL:]
            fmt = tail[:, :F3].reshape(C, 3, MAX_FMT_GENES)
            sg = tail[:, F3:]
            out = veval(perm, til, fmt, sg, *consts)
            big = jnp.float32(jnp.inf)
            kedp = jnp.where(ok, out["cycles"] * out["energy_pj"], big)
            canon = jnp.concatenate([perm, til, tail], axis=1)
            canon = jnp.where(ok[:, None], canon, 0)
            new_pop = jnp.concatenate([elites, kids], axis=0)
            new_edp = jnp.concatenate([elite_edp, kedp], axis=0)
            ys = dict(canon=canon, valid=ok & out["valid"],
                      energy_pj=jnp.where(ok, out["energy_pj"], big),
                      cycles=jnp.where(ok, out["cycles"], big))
            return (new_pop, new_edp), ys

        (pop, edp), ys = jax.lax.scan(step, (pop, edp), draws)
        return pop, edp, ys

    return jax.vmap(_named(one_task, SCAN_PROGRAM),
                    in_axes=(0, 0, 0, 0, 0, 0))


@lru_cache(maxsize=32)
def _direct_scan_fn(d: int, n_pad: int, topo: Topology, dens_key: str,
                    n_parents: int, n_elite: int, genes_per: int):
    fn = jax.jit(_per_task_carry(_direct_scan_task_fn(
        d, n_pad, topo, dens_key, n_parents, n_elite, genes_per)),
                 donate_argnums=_donate_args())
    with _LOCK:
        _JIT_FNS[(d, n_pad, topo.fingerprint, dens_key,
                  f"dscan:p{n_parents}e{n_elite}g{genes_per}")] = fn
    return fn


def _sharded_scan_fn(d: int, n_pad: int, topo: Topology, dens_key: str,
                     n_parents: int, n_elite: int, genes_per: int, mesh):
    """The scan program shard_map-ed over the task axis of ``mesh``'s
    first axis (task count must divide the device count's multiple —
    checked by the caller)."""
    key = (d, n_pad, topo.fingerprint, dens_key,
           f"scan:p{n_parents}e{n_elite}g{genes_per}", _mesh_key(mesh))
    fn = _SHARD_FNS.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as P
        vfn = _scan_task_fn(d, n_pad, topo, dens_key, n_parents, n_elite,
                            genes_per)
        ax = mesh.axis_names[0]
        fn = jax.jit(_per_task_carry(jax.shard_map(
            vfn, mesh=mesh, in_specs=(P(ax),) * 7, out_specs=P(ax))))
        with _LOCK:
            _SHARD_FNS[key] = fn
            _JIT_FNS[(d, n_pad, topo.fingerprint, dens_key,
                      f"scan:p{n_parents}e{n_elite}g{genes_per}"
                      f"@{_mesh_ndev(mesh)}")] = fn
    return fn


def _sharded_stacked_fn(d: int, n_pad: int, topo: Topology,
                        dens_key: str, layout: Tuple, mesh):
    """The stacked mega-batch program shard_map-ed over batch rows, the
    per-task table replicated on every device."""
    key = (d, n_pad, topo.fingerprint, dens_key, "stacked",
           _mesh_key(mesh))
    fn = _SHARD_FNS.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as P
        ax = mesh.axis_names[0]
        fn = jax.jit(jax.shard_map(
            _stacked_program(d, n_pad, topo, dens_key, layout), mesh=mesh,
            in_specs=(P(ax), P()), out_specs=P(None, ax)))
        with _LOCK:
            _SHARD_FNS[key] = fn
            _JIT_FNS[(d, n_pad, topo.fingerprint, dens_key,
                      f"stacked@{_mesh_ndev(mesh)}")] = fn
    return fn


def _padded_layout(model: "JaxCostModel") -> PaddedLayout:
    lay = getattr(model, "_pad_layout", None)
    if lay is None:
        lay = PaddedLayout(model.spec, model.n_pad)
        model._pad_layout = lay
    return lay


def scan_slots(n: int, cap: Optional[int] = None) -> int:
    """The task slots a scan dispatch of ``n`` same-shape tasks runs
    with: the next power of two, or ``cap`` — the most tasks the group
    can hold, where the caller knows it — when that is smaller.  A
    served fleet's task count moves with every admission and retirement;
    the buckets hold the scan programs of one (signature, segment shape)
    to a few, whatever the traffic."""
    slots = 1 << max(0, n - 1).bit_length()
    return cap if cap is not None and n <= cap < slots else slots


def _scan_call(sig: Tuple, tag: str, kind: str, fn, tasks: List[Tuple],
               filler: Callable[[], Tuple], task_rows: int,
               shape: Tuple[int, int, int], cap: Optional[int] = None,
               sharded: Optional[Callable] = None, mesh=None) -> Tuple:
    """Dispatch one scan over ``tasks`` (each task's program inputs:
    pop, edp, then the rest), its task axis padded to
    :func:`scan_slots`.  The spare slots take ``filler()``: a real
    task's inputs with its host-side population, so valid genomes in
    fresh buffers (the per-task carries are donated); their outputs are
    never read.  ``shape`` is the AOT key's (B, k, n_children);
    ``sharded`` builds the mesh program, used when the slots divide
    over the mesh's devices."""
    T = len(tasks)
    slots = scan_slots(T, cap)
    if slots > T:
        tasks = tasks + [filler() for _ in range(slots - T)]
    args = (tuple(jnp.asarray(t[0]) for t in tasks),
            tuple(jnp.asarray(t[1]) for t in tasks)) + tuple(
        jax.tree_util.tree_map(lambda *xs: jnp.asarray(np.stack(xs)),
                               *[t[2:] for t in tasks]))
    _count_dispatch()
    trace.count(ROWS, T * task_rows, sig=sig, kind=kind)
    trace.count(ROWS_PADDED, (slots - T) * task_rows, sig=sig, kind=kind)
    trace.count(SCAN_TASKS, T, sig=sig, slots=slots)
    key = sig + (tag, slots) + shape
    ndev = _mesh_ndev(mesh)
    if sharded is not None and ndev > 1 and slots % ndev == 0:
        out = sharded()(*args)
        _note_scan_build(sig + (f"{tag}@{ndev}", slots) + shape, "inline")
        return out
    out = _aot_call(key, fn, args)
    _note_scan_build(key, "inline")
    return out


def _segment_results(models: Sequence["JaxCostModel"], out: Tuple,
                     gens_of: Callable, defer: bool,
                     restart: int = 0) -> List[SegmentResult]:
    """One SegmentResult per real task of a scan dispatch: each carries
    its own (pop, edp) output; its ``gens_of(t, model, pop, edp, ys)``
    reads its slot of the stacked outputs, converted to numpy once for
    the whole dispatch on the first resolve."""
    pop_f, edp_f, ys, pops, edps = out
    host = {}

    def materialize():
        if "ys" not in host:
            def conv():
                return (np.asarray(pop_f), np.asarray(edp_f),
                        {kk: np.asarray(v) for kk, v in ys.items()})
            host["pf"], host["ef"], host["ys"] = _time_block(conv)
        return host["pf"], host["ef"], host["ys"]

    def make_harvest(t, m):
        return lambda: gens_of(t, m, *materialize())

    results: List[SegmentResult] = []
    for t, m in enumerate(models):
        r = SegmentResult(gens=None, final_pop=None, final_edp=None,
                          carry=(pops[t], edps[t]),
                          harvest=make_harvest(t, m))
        if not defer:
            r.resolve()
        if restart:
            _, _, ys_h = materialize()
            r.state = (float(ys_h["best"][t, 0]),
                       int(ys_h["since"][t, 0]))
        results.append(r)
    return results


def run_segments(models: Sequence["JaxCostModel"],
                 segs: Sequence[DeviceSegment],
                 mesh=None, defer: bool = False,
                 cap: Optional[int] = None) -> List[SegmentResult]:
    """Execute one DeviceSegment per model as a SINGLE device dispatch:
    all segments (which must share the models' compilation signature and
    the segment shape key) stack along a task axis, and a jitted
    vmap-of-lax.scan advances every task ``k`` generations on-device.

    The task axis is padded to :func:`scan_slots` (``cap``: the most
    tasks this group can hold, where the caller knows it), so a fleet
    whose task count changes runs a few programs, not one per count;
    the spare slots repeat a real task's inputs and are dropped.

    Host work per call is limited to padding genomes/plan arrays into
    the shared scan layout and, afterwards, slicing the per-generation
    outputs back per task (``_canonical``-recomputed like every other
    dispatch path).  With ``mesh`` given and the task slots divisible by
    the device count, tasks shard across devices via ``jax.shard_map``;
    otherwise the single-device program runs unchanged.

    Pipelining hooks: a segment carrying ``carry`` (the device-resident
    padded (pop, edp) of its previous SegmentResult) skips the host-side
    genome padding entirely — the population never leaves the device
    between rounds.  With ``defer=True`` the returned results hold a
    ``harvest`` thunk instead of materialized numpy gens; the device is
    already computing when this function returns, and the caller
    converts (``SegmentResult.resolve``) one round late.  ``carry`` and
    the device handles are valid either way, so the next segment can
    dispatch before the previous one is harvested.

    ``kind == "direct"`` segments (``standard_es``) route to the
    direct-genome scan; ``restart > 0`` runs the stagnation-restart
    kernel variant and needs ``seg.state`` (best-so-far, stagnant-gens)
    plus per-generation ``draws["fresh"]`` re-init blocks."""
    if len(models) != len(segs):
        raise ValueError("models and segments must pair up")
    sig = models[0].signature
    if any(m.signature != sig for m in models):
        raise ValueError(
            f"run_segments needs one shared signature, got "
            f"{sorted({m.signature for m in models})}")
    shape_key = segment_shape_key(segs[0])
    if any(segment_shape_key(s) != shape_key for s in segs):
        raise ValueError("run_segments needs one shared segment shape")
    B, k, n_parents, n_elite, genes_per, kind, restart = shape_key
    if kind == "direct":
        return _run_direct_segments(models, segs, defer=defer, cap=cap)

    def inputs(m, s, carry=True):
        lay = _padded_layout(m)
        if carry and s.carry is not None:
            pop, edp = s.carry
        else:
            pop = lay.pad_rows(np.asarray(s.pop, dtype=np.int32))
            edp = np.asarray(s.edp, dtype=np.float32)
        fm = np.zeros(lay.Lp, dtype=bool)
        fv = np.zeros(lay.Lp, dtype=np.int32)
        if s.fixed_genes:
            idx = lay.pad_index(
                np.asarray(list(s.fixed_genes), dtype=np.int64))
            fm[idx] = True
            fv[idx] = np.asarray(list(s.fixed_genes.values()),
                                 dtype=np.int32)
        dr = dict(s.draws)
        dr["gene"] = lay.pad_index(dr["gene"]).astype(np.int32)
        dr["cuts"] = lay.pad_cut(dr["cuts"]).astype(np.int32)
        if restart:
            fr = np.asarray(dr["fresh"], dtype=np.int32)
            gk, gc = fr.shape[0], fr.shape[1]
            dr["fresh"] = lay.pad_rows(
                fr.reshape(gk * gc, -1)).reshape(gk, gc, -1)
            dr["best0"] = np.asarray([s.state[0]], dtype=np.float32)
            dr["since0"] = np.asarray([s.state[1]], dtype=np.int32)
        return (pop, edp, lay.pad_vector(m.spec.gene_ub.astype(np.int32), 1),
                fm, fv, dr, m._np_consts)

    n_children = segs[0].draws["ab"].shape[1]
    task_rows = k * n_children
    if restart:
        task_rows += k * np.shape(segs[0].draws["fresh"])[1]
    tag = f"scan:p{n_parents}e{n_elite}g{genes_per}" + (
        f"r{restart}" if restart else "")
    topo = models[0].arch.topology
    with trace.span("fleet.dispatch", sig=sig, kind="scan"):
        out = _scan_call(
            sig, tag, "scan",
            _scan_fn(sig[0], sig[1], topo, sig[3], n_parents, n_elite,
                     genes_per, restart),
            [inputs(m, s) for m, s in zip(models, segs)],
            lambda: inputs(models[0], segs[0], carry=False), task_rows,
            (B, k, n_children), cap,
            sharded=None if restart else lambda: _sharded_scan_fn(
                sig[0], sig[1], topo, sig[3], n_parents, n_elite,
                genes_per, mesh), mesh=mesh)

    def gens_of(t, m, pf, ef, ys_h):
        lay = _padded_layout(m)
        gens = []
        for g in range(k):
            kids = lay.unpad_rows(ys_h["kids"][t, g]).astype(np.int64)
            kout = _canonical(dict(valid=ys_h["valid"][t, g],
                                   energy_pj=ys_h["energy_pj"][t, g],
                                   cycles=ys_h["cycles"][t, g]))
            if restart:
                kout["fresh"] = _canonical(dict(
                    valid=ys_h["f_valid"][t, g],
                    energy_pj=ys_h["f_energy_pj"][t, g],
                    cycles=ys_h["f_cycles"][t, g]))
                kout["restarted"] = bool(ys_h["restarted"][t, g])
            gens.append((kids, kout))
        return gens, lay.unpad_rows(pf[t]).astype(np.int64), ef[t]

    return _segment_results(models, out, gens_of, defer, restart)


def _run_direct_segments(models: Sequence["JaxCostModel"],
                         segs: Sequence[DeviceSegment],
                         defer: bool = False,
                         cap: Optional[int] = None) -> List[SegmentResult]:
    """:func:`run_segments` for ``kind == "direct"`` segments: the carry
    population lives in DIRECT value coordinates and the in-scan
    translation (see ``_direct_scan_task_fn``) produces the canonical
    rows each generation's ``gens`` report.  ``final_pop`` is returned
    in direct coordinates (the generator's mirror), while ``gens`` kid
    rows are canonical genomes with untranslatable rows zeroed — exactly
    the legacy ``direct_requests`` registration rows."""
    sig = models[0].signature
    shape_key = segment_shape_key(segs[0])
    B, k, n_parents, n_elite, genes_per, kind, restart = shape_key
    if restart:
        raise ValueError("direct segments do not support in-scan restart")

    def inputs(m, s, carry=True):
        if carry and s.carry is not None:
            pop, edp = s.carry
        else:
            pop = np.asarray(s.pop, dtype=np.int32)
            edp = np.asarray(s.edp, dtype=np.float32)
        return (pop, edp, np.asarray(s.aux["scramble"], dtype=np.int32),
                np.asarray(s.aux["dim_sizes"], dtype=np.float32),
                {kk: np.asarray(v) for kk, v in s.draws.items()},
                m._np_consts)

    n_children = segs[0].draws["ab"].shape[1]
    topo = models[0].arch.topology
    with trace.span("fleet.dispatch", sig=sig, kind="dscan"):
        out = _scan_call(
            sig, f"dscan:p{n_parents}e{n_elite}g{genes_per}", "dscan",
            _direct_scan_fn(sig[0], sig[1], topo, sig[3], n_parents,
                            n_elite, genes_per),
            [inputs(m, s) for m, s in zip(models, segs)],
            lambda: inputs(models[0], segs[0], carry=False),
            k * n_children, (B, k, n_children), cap)

    def gens_of(t, m, pf, ef, ys_h):
        lay = _padded_layout(m)
        gens = []
        for g in range(k):
            kids = lay.unpad_rows(ys_h["canon"][t, g]).astype(np.int64)
            kout = _canonical(dict(valid=ys_h["valid"][t, g],
                                   energy_pj=ys_h["energy_pj"][t, g],
                                   cycles=ys_h["cycles"][t, g]))
            gens.append((kids, kout))
        return gens, pf[t].astype(np.int64), ef[t]

    return _segment_results(models, out, gens_of, defer)


# ---------------------------------------------------------------- wrapper


class JaxCostModel:
    """Batch evaluator bound to one (workload, arch/platform) pair.
    Instances with the same (ndims, prime bucket, topology) share a
    single XLA compilation — same-topology platforms (e.g. the paper's
    edge/mobile/cloud) differ only in the traced parameter vector.

    ``n_pad`` widens the prime axis beyond the workload's natural bucket so
    a group of concurrent searches over different workloads can be forced
    onto ONE compilation signature (``search.MultiSearch``); the padding
    primes are 1.0 and are numerically inert.

    ``structured`` likewise promotes an all-uniform workload onto the
    structured-density kernel variant (its Uniform models become traced
    family rows) so a mixed uniform/banded/N:M fleet shares one
    signature; ``None`` picks the workload's natural mode — all-uniform
    workloads then compile the literal pre-density-model kernel,
    bit-identical to the goldens."""

    def __init__(self, spec: GenomeSpec,
                 platform: Union[str, Platform, ArchSpec],
                 n_pad: Optional[int] = None,
                 structured: Optional[bool] = None):
        self.spec = spec
        self.arch = as_arch(platform)
        self.platform = self.arch          # legacy alias
        if self.arch.topology != spec.arch.topology:
            raise ValueError(
                f"GenomeSpec was built for arch {spec.arch.name!r} but "
                f"the evaluator targets {self.arch.name!r} with a "
                f"different topology")
        wl = spec.workload
        d = wl.ndims
        self.d = d
        self.n_primes = spec.n_primes
        self.n_pad = _bucket(max(self.n_primes, 1, int(n_pad or 0)))
        natural_structured = wl.structured_density
        if structured is None:
            structured = natural_structured
        elif not structured and natural_structured:
            raise ValueError(
                f"workload {wl.name!r} declares structured density "
                f"models; it cannot run on the uniform kernel")
        self.structured = bool(structured)
        self.dens_key = "u" if not self.structured else \
            "s:" + density_lib.registry_fingerprint()

        primes = np.ones(self.n_pad, dtype=np.float32)
        prime_dim = np.zeros(self.n_pad, dtype=np.int32)
        dim_idx = {dim: i for i, dim in enumerate(wl.dim_order)}
        for i, (dd, p) in enumerate(spec.primes):
            primes[i] = p
            prime_dim[i] = dim_idx[dd]
        # numpy copies feed the scans and the AOT structs, jnp copies the
        # broadcast kernel, and one float32 row of them all the per-task
        # table of eval_stacked
        self._np_consts = (
            primes,
            prime_dim,
            np.asarray([[dim in t.dims for dim in wl.dim_order]
                        for t in wl.tensors], bool),
            np.asarray([wl.density_of(t.name) for t in wl.tensors],
                       np.float32),
            np.asarray([t.size(wl.dim_sizes) for t in wl.tensors],
                       np.float32),
            np.float32(wl.macs),
            np.asarray([1.0 if t.is_output else 0.0 for t in wl.tensors],
                       np.float32),
            self.arch.param_vector(),
            # per-tensor traced density rows [code, hit, family params..]
            np.asarray([density_lib.param_row(wl.density_model_of(t.name))
                        for t in wl.tensors], np.float32))
        (self._primes, self._prime_dim, self._relevance, self._densities,
         self._full_elems, self._total_macs, self._z_onehot, self._plat,
         self._dens_params) = [jnp.asarray(c) for c in self._np_consts]
        self._const_layout = tuple((np.shape(c), np.asarray(c).dtype.str)
                                   for c in self._np_consts)
        self._const_row = np.concatenate(
            [np.asarray(c, np.float32).reshape(-1)
             for c in self._np_consts])
        # the row of eval_stacked's table this model's rows read
        self._table_key = (wl.cache_key(), self.arch)

        self._fn = _jitted_eval(d, self.n_pad, self.arch.topology,
                                self.dens_key)
        # genome segment -> first column of the packed row buffer
        s = spec.segments
        tt = _topo_tables(self.arch.topology)
        self._nl = nl = tt.n_levels
        f = nl + self.n_pad
        self._pack_cols = [(s["perm"].slice, 0), (s["tiling"].slice, nl)] + [
            (s[f"fmt_{t.name}"].slice, f + i * MAX_FMT_GENES)
            for i, t in enumerate(wl.tensors)] + [
            (s["sg"].slice, f + 3 * MAX_FMT_GENES)]
        self._row_width = f + 3 * MAX_FMT_GENES + tt.n_sites + 1

    @property
    def signature(self) -> Tuple[int, int, str, str]:
        """The (ndims, prime-bucket, topology, density-key) compilation
        signature."""
        return (self.d, self.n_pad, self.arch.topology.fingerprint,
                self.dens_key)

    def _pack(self, genomes, out: np.ndarray) -> None:
        """Write a (B, L) genome batch into ``out``, a zeroed (B, W) slice
        of a packed int32 row buffer: perm | tiling, zero-padded to the
        prime bucket | fmt, tensor by tensor | sg | the row's task index
        (the caller's).  For one compilation signature the columns are
        the same across workloads — the property mega-batch stacking
        relies on; :func:`_split_rows` reads them back."""
        genomes = np.asarray(genomes)
        for sl, col in self._pack_cols:
            out[:, col:col + sl.stop - sl.start] = genomes[:, sl]

    def __call__(self, genomes) -> Dict[str, np.ndarray]:
        """genomes: (B, L) ints -> dict of (B,) arrays.  Pads the batch to
        the next power of two and the prime axis to its bucket."""
        n = len(genomes)
        padded = _pad_batch(n)
        sig = self.signature
        with trace.span("fleet.dispatch", sig=sig, kind="bcast"):
            rows = np.zeros((padded, self._row_width), np.int32)
            self._pack(genomes, rows[:n])
            perm, til, fmt, sg = _split_rows(rows, self._nl, self.n_pad)
            _count_dispatch()
            trace.count(ROWS, n, sig=sig, kind="bcast")
            trace.count(ROWS_PADDED, padded - n, sig=sig, kind="bcast")
            out = _aot_call(
                sig + ("bcast", padded), self._fn,
                (jnp.asarray(perm), jnp.asarray(til),
                 jnp.asarray(fmt), jnp.asarray(sg),
                 self._primes, self._prime_dim, self._relevance,
                 self._densities, self._full_elems, self._total_macs,
                 self._z_onehot, self._plat, self._dens_params))
        return _canonical(_time_block(
            lambda: {k: np.asarray(v)[:n] for k, v in out.items()}))

    def run_segment(self, seg: DeviceSegment) -> SegmentResult:
        """Execute one device-resident ES segment against this model
        (the single-task case of :func:`run_segments`).  ``_drive`` and
        other single-evaluator drivers discover this method by name —
        evaluators without it receive ``None`` and the generator replays
        the segment on the host."""
        return run_segments([self], [seg])[0]


def _pad_batch(n: int) -> int:
    """Batch-axis padding shared by every dispatch path: next power of
    two, floor 64 — ES populations and the baselines' odd native batch
    sizes (48, 50, 64) all land on the same few warm shapes."""
    return max(64, 1 << max(0, (n - 1)).bit_length())


def _canonical(out: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Recompute the derived outputs (edp, log10_edp) in numpy from the
    kernel's float32 cycles/energy.  XLA is free to fuse the final
    ``cycles * energy`` differently in the broadcast vs stacked kernel
    (observed: 1-ULP drift), so deriving them outside the jit makes every
    dispatch path bit-identical for the same rows.  Keys come back in
    sorted order, as a jitted program's dict output has them."""
    cycles = out["cycles"]
    energy = out["energy_pj"]
    with np.errstate(over="ignore"):
        out["edp"] = cycles * energy
        out["log10_edp"] = (np.log10(np.maximum(cycles, 1e-30)) +
                            np.log10(np.maximum(energy, 1e-30))
                            ).astype(cycles.dtype)
    return dict(sorted(out.items()))


# ------------------------------------------------- per-task constants table

# eval_stacked's workload/platform constants live on the device: per
# signature, one float32 table with a row per distinct (workload, arch)
# of the group (``JaxCostModel._const_row``), gathered per genome row by
# the row's task index inside the program.  The table is uploaded again
# only when the group's set of tasks changes (an admission or a
# retirement), never for a change of row counts or padding.  Keys are
# CONTENT (workload cache_key + arch), never id(), so a recycled object
# can't alias a stale table.  Its slots are a power of two, at least
# TABLE_FLOOR and never shrinking per signature, so a served group's
# table keeps one shape whatever its make-up and compile-ahead can build
# it (:func:`table_slots`).
_STACK_TABLES: Dict[Tuple[int, int, str, str], "_Table"] = {}

#: the fewest slots a per-task table has: one shape for every group of
#: up to this many distinct tasks
TABLE_FLOOR = 16


class _Table(NamedTuple):
    row_of: Dict[Tuple, int]    # (cache_key, arch) -> table row
    dev: jax.Array              # (slots, width) float32, on the device


def stack_prep_counts() -> Tuple[int, int]:
    """(table reuses, table uploads) of the per-task constants tables
    :func:`eval_stacked` keeps on the device."""
    return int(_since_reset(PREP_HITS)), int(_since_reset(PREP_MISSES))


def reset_stack_prep_counts() -> None:
    _reset(PREP_HITS, PREP_MISSES)


def table_slots(sig: Tuple, n: int) -> int:
    """The slots of ``sig``'s per-task table for a group of ``n``
    distinct tasks: the next power of two, at least :data:`TABLE_FLOOR`
    and at least the slots the signature's table already has."""
    slots = max(TABLE_FLOOR, 1 << max(0, n - 1).bit_length())
    with _LOCK:
        held = _STACK_TABLES.get(sig)
    return slots if held is None else max(slots, held.dev.shape[0])


def _stack_table(models: Sequence["JaxCostModel"]) -> _Table:
    """The device table of the group's per-task constants: the one held
    for the signature when it holds the same tasks (counted a reuse),
    else a new one uploaded in one copy (counted an upload)."""
    sig = models[0].signature
    first: Dict[Tuple, "JaxCostModel"] = {}
    for m in models:
        first.setdefault(m._table_key, m)
    with _LOCK:
        held = _STACK_TABLES.get(sig)
    if held is not None and held.row_of.keys() == first.keys():
        trace.count(PREP_HITS)
        return held
    trace.count(PREP_MISSES)
    rows = np.empty((table_slots(sig, len(first)),
                     models[0]._const_row.size), np.float32)
    rows[:] = models[0]._const_row          # unused slots: never read
    for i, m in enumerate(first.values()):
        rows[i] = m._const_row
    table = _Table({k: i for i, k in enumerate(first)}, jnp.asarray(rows))
    trace.count(TRANSFERS, dir="h2d", what="table")
    with _LOCK:
        _STACK_TABLES[sig] = table
    return table


class StackedPending:
    """Handle to an in-flight ``eval_stacked(..., defer=True)`` dispatch:
    the device is computing when this is constructed, and ``finalize()``
    blocks (charged to :func:`host_blocked_s`) on the one copy of the
    (3, padded) result back, canonicalizes, and slices the mega-batch
    back per task.  ``finalize`` is idempotent."""

    def __init__(self, out, sizes: Sequence[int]):
        self._out = out
        self._sizes = list(sizes)
        self._sliced: Optional[List[Dict[str, np.ndarray]]] = None

    def finalize(self) -> List[Dict[str, np.ndarray]]:
        if self._sliced is None:
            out = self._out
            res = _time_block(lambda: np.asarray(out))
            trace.count(TRANSFERS, dir="d2h", what="out")
            flat = _canonical(dict(valid=res[0] != 0, energy_pj=res[1],
                                   cycles=res[2]))
            sliced: List[Dict[str, np.ndarray]] = []
            off = 0
            for n in self._sizes:
                sliced.append({k: v[off:off + n] for k, v in flat.items()})
                off += n
            self._sliced = sliced
            self._out = None
        return self._sliced


def stacked_rows(total: int, pad_floor: int = 0, mesh=None) -> int:
    """The rows :func:`eval_stacked` dispatches for ``total`` genome
    rows: the power-of-two rule raised to ``pad_floor``, then to a
    multiple of the mesh's device count."""
    padded = max(_pad_batch(total), int(pad_floor))
    ndev = _mesh_ndev(mesh)
    if ndev > 1 and padded % ndev:
        padded = -(-padded // ndev) * ndev
    return padded


def eval_stacked(models: Sequence["JaxCostModel"],
                 batches: Sequence[np.ndarray],
                 pad_floor: int = 0,
                 mesh=None, defer: bool = False):
    """Evaluate several (model, genome-batch) pairs sharing one
    compilation signature in a SINGLE device dispatch.

    The batches are packed into one zeroed (padded, W) int32 row buffer
    (perm | tiling | fmt | sg | task index, see
    :meth:`JaxCostModel._pack`) and sent in one host-to-device copy; each
    row gathers its workload/platform constants from the signature's
    per-task table on the device (:func:`_stack_table`, uploaded again
    only when the group's set of tasks changes — see
    :func:`stack_prep_counts`); the program returns one (3, padded)
    float32 array (valid, energy_pj, cycles), fetched in one copy and
    sliced back per input pair.  Rows are evaluated by exactly the same
    per-row computation as the broadcast kernel, so results are
    bit-identical to per-model calls.  Each copy counts in the recorder's
    :data:`TRANSFERS` (``dir`` h2d/d2h, ``what`` rows/table/out).

    ``pad_floor`` raises the batch padding beyond the power-of-two rule —
    drivers pass the watermark of earlier rounds so a shrinking fleet
    keeps hitting an already-compiled mega-batch shape instead of tracing
    a new one (padding rows are zero genomes reading the first model's
    constants, sliced off).

    ``mesh`` shards the padded rows across the mesh's devices via
    ``jax.shard_map``, the table replicated (rows are further padded to a
    device-count multiple — a no-op for the usual power-of-two shapes);
    with ``mesh=None`` (or one device) the single-device path runs
    unchanged, and per-row results are identical either way because both
    wrap the same program.

    ``defer=True`` returns a :class:`StackedPending` instead of the
    sliced list: the dispatch has been issued (JAX async dispatch keeps
    the device busy) but no host-blocking conversion happens until
    ``finalize()`` — the pipelined driver finalizes round N while round
    N+1 computes.  Results are bit-identical to ``defer=False`` because
    finalize performs exactly the conversion this function otherwise
    does inline."""
    if len(models) != len(batches):
        raise ValueError("models and batches must pair up")
    sig = models[0].signature
    if any(m.signature != sig for m in models):
        raise ValueError(
            f"eval_stacked needs one shared signature, got "
            f"{sorted({m.signature for m in models})}")
    sizes = [len(b) for b in batches]
    total = sum(sizes)
    padded = stacked_rows(total, pad_floor, mesh)
    ndev = _mesh_ndev(mesh)
    m0 = models[0]
    with trace.span("fleet.dispatch", sig=sig, kind="stacked"):
        table = _stack_table(models)
        rows = np.zeros((padded, m0._row_width), np.int32)
        off = 0
        for m, b in zip(models, batches):
            m._pack(b, rows[off:off + len(b)])
            rows[off:off + len(b), -1] = table.row_of[m._table_key]
            off += len(b)
        rows[total:, -1] = table.row_of[m0._table_key]
        _count_dispatch()
        trace.count(ROWS, total, sig=sig, kind="stacked")
        trace.count(ROWS_PADDED, padded - total, sig=sig, kind="stacked")
        args = (jnp.asarray(rows), table.dev)
        trace.count(TRANSFERS, dir="h2d", what="rows")
        if ndev > 1:
            fn = _sharded_stacked_fn(sig[0], sig[1], m0.arch.topology,
                                     sig[3], m0._const_layout, mesh)
            out = fn(*args)
        else:
            fn = _stacked_fn(sig[0], sig[1], m0.arch.topology, sig[3],
                             m0._const_layout)
            out = _aot_call(sig + ("stacked", padded,
                                   table.dev.shape[0]), fn, args)
        pending = StackedPending(out, sizes)
    if defer:
        return pending
    return pending.finalize()


# ----------------------------------------------- compile-ahead job prep
#
# Builders for the (key, jit_fn, arg_structs) triples ``compile_ahead``
# consumes.  Each mirrors EXACTLY the argument pytree its dispatch path
# passes — the AOT registry key doubles as the contract: if the builder
# and the dispatch ever disagree on shapes/dtypes the executable is
# either not found or fails its call loudly (``compile_ahead_errors``),
# never a wrong answer.


def _row_structs(model: "JaxCostModel", padded: int) -> Tuple:
    tt = _topo_tables(model.arch.topology)
    S = jax.ShapeDtypeStruct
    return (S((padded, tt.n_levels), np.int32),
            S((padded, model.n_pad), np.int32),
            S((padded, 3, MAX_FMT_GENES), np.int32),
            S((padded, tt.n_sites), np.int32))


def stacked_compile_job(model: "JaxCostModel", padded: int,
                        slots: Optional[int] = None) -> Tuple:
    """AOT job for one ``eval_stacked`` mega-batch shape: ``padded``
    packed rows against a per-task table of ``slots`` rows (by default
    the slots :func:`table_slots` gives a one-task group now)."""
    sig = model.signature
    if slots is None:
        slots = table_slots(sig, 1)
    fn = _stacked_fn(sig[0], sig[1], model.arch.topology, sig[3],
                     model._const_layout)
    S = jax.ShapeDtypeStruct
    return (sig + ("stacked", padded, slots), fn,
            (S((padded, model._row_width), np.int32),
             S((slots, model._const_row.size), np.float32)))


def bcast_compile_job(model: "JaxCostModel", padded: int) -> Tuple:
    """AOT job for one broadcast (per-task ``model(genomes)``) shape."""
    sig = model.signature
    S = jax.ShapeDtypeStruct
    consts = tuple(S(np.shape(np.asarray(c)), np.asarray(c).dtype)
                   for c in model._np_consts)
    return (sig + ("bcast", padded), model._fn,
            _row_structs(model, padded) + consts)


def _draw_structs(T: int, k: int, n_children: int, genes_per: int) -> Dict:
    S = jax.ShapeDtypeStruct
    return dict(ab=S((T, k, n_children, 2), np.int32),
                cuts=S((T, k, n_children), np.int32),
                active=S((T, k, n_children), np.bool_),
                gene=S((T, k, n_children, genes_per), np.int32),
                vals=S((T, k, n_children, genes_per), np.int32))


def _seg_consts_structs(model: "JaxCostModel", T: int) -> Tuple:
    S = jax.ShapeDtypeStruct
    return tuple(S((T,) + np.shape(np.asarray(c)), np.asarray(c).dtype)
                 for c in model._np_consts)


def _carry_structs(T: int, B: int, width: int) -> Tuple:
    S = jax.ShapeDtypeStruct
    return (tuple(S((B, width), np.int32) for _ in range(T)),
            tuple(S((B,), np.float32) for _ in range(T)))


def scan_compile_job(model: "JaxCostModel", B: int, k: int,
                     n_parents: int, n_elite: int, genes_per: int,
                     T: int, restart: int = 0) -> Tuple:
    """AOT job for one ``run_segments`` ES-scan shape (``T`` task slots,
    a :func:`scan_slots` bucket, of ``B`` genomes advanced ``k``
    generations)."""
    sig = model.signature
    fn = _scan_fn(sig[0], sig[1], model.arch.topology, sig[3],
                  n_parents, n_elite, genes_per, restart)
    lay = _padded_layout(model)
    n_children = B - n_elite
    S = jax.ShapeDtypeStruct
    draws = _draw_structs(T, k, n_children, genes_per)
    if restart:
        draws["fresh"] = S((T, k, n_children, lay.Lp), np.int32)
        draws["best0"] = S((T, 1), np.float32)
        draws["since0"] = S((T, 1), np.int32)
    tag = f"scan:p{n_parents}e{n_elite}g{genes_per}" + (
        f"r{restart}" if restart else "")
    args = _carry_structs(T, B, lay.Lp) + (
        S((T, lay.Lp), np.int32), S((T, lay.Lp), np.bool_),
        S((T, lay.Lp), np.int32), draws,
        _seg_consts_structs(model, T))
    return sig + (tag, T, B, k, n_children), fn, args


def direct_scan_compile_job(model: "JaxCostModel", B: int, k: int,
                            n_parents: int, n_elite: int, genes_per: int,
                            T: int, direct_len: int,
                            n_perm_codes: int) -> Tuple:
    """AOT job for one ``standard_es`` direct-scan shape.  ``direct_len``
    and ``n_perm_codes`` come from the task's ``DirectValueSpec``."""
    sig = model.signature
    fn = _direct_scan_fn(sig[0], sig[1], model.arch.topology, sig[3],
                         n_parents, n_elite, genes_per)
    n_children = B - n_elite
    S = jax.ShapeDtypeStruct
    args = _carry_structs(T, B, direct_len) + (
        S((T, n_perm_codes), np.int32), S((T, model.d), np.float32),
        _draw_structs(T, k, n_children, genes_per),
        _seg_consts_structs(model, T))
    return (sig + (f"dscan:p{n_parents}e{n_elite}g{genes_per}",
                   T, B, k, n_children), fn, args)
