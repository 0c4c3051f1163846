"""Public search facade: run any optimization method on (workload,
platform) under an evaluation budget.

    from repro.core import search
    res = search.run("sparsemap", workload, "cloud", budget=20_000, seed=0)
    print(res.best_edp, res.valid_fraction)
    design = search.decode_best(workload, res)

Evaluator instances are cached per (workload content, platform) because
jit compilation of the batch cost model dominates small searches; the key
is :meth:`Workload.cache_key`, so content-equal workloads share one
evaluator and a recycled object id can never alias a stale entry.

Concurrent sweeps use :class:`MultiSearch`, the repo's method-agnostic
search runtime: every task — any (method, workload, platform) triple whose
method has a request generator in ``baselines.REQUEST_METHODS`` — is a
generator that yields genome batches, and each round every pending task's
batch is evaluated and its generator advanced.  Tasks are ordered by
(ndims, prime-bucket, topology) compilation signature; with
``align_signatures=True``
each workload's prime axis is padded up to the largest bucket among its
same-ndims peers so the whole group shares ONE XLA compilation, and all
same-signature pending batches are concatenated into one padded
mega-batch per round — a single device dispatch per signature instead of
one per task:

    results = search.run_sweep([wl_a, wl_b], "cloud", budget=20_000)
    grid = search.run_method_sweep(["sparsemap", "pso", "random_mapper"],
                                   [wl_a, wl_b], "cloud", budget=20_000)
"""
from __future__ import annotations

import dataclasses
import json
import time
import warnings
from collections import Counter
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np

from . import accel, es_ops, jax_cost, trace
from .arch import ArchSpec, as_arch
from .baselines import (METHODS, REQUEST_METHODS, SEGMENT_METHODS,
                        make_requests)
from .es_ops import DeviceSegment
from .cost_model import CostReport, Design, evaluate
from .encoding import GenomeSpec
from .evolution import SearchResult, _Budget
from .jax_cost import JaxCostModel, _bucket
from .workload import Workload, workload_from_dict, workload_to_dict

#: anything that names hardware: a Platform/arch name, a Platform, or an
#: ArchSpec (see repro.core.arch.as_arch)
PlatformLike = Union[str, accel.Platform, ArchSpec]

_CACHE: Dict[Tuple[Tuple, ArchSpec, Optional[int], bool],
             Tuple[GenomeSpec, JaxCostModel]] = {}


def _platform(platform: PlatformLike) -> ArchSpec:
    """Resolve any hardware description to its ArchSpec."""
    return as_arch(platform)


def get_evaluator(workload: Workload, platform: PlatformLike,
                  n_pad: Optional[int] = None,
                  structured: bool = False
                  ) -> Tuple[GenomeSpec, JaxCostModel]:
    plat = _platform(platform)
    # ``structured=True`` promotes an all-uniform workload onto the
    # structured-density kernel so it can mega-batch with banded/N:M
    # peers (MultiSearch alignment); a naturally structured workload is
    # normalized to its natural key so sequential and fleet runs share
    # one evaluator
    structured = bool(structured) and not workload.structured_density
    # the ArchSpec itself (content-hashable) keys the cache: two specs
    # that merely share a NAME must not alias one evaluator (same
    # aliasing class as the id(workload) bug fixed in PR 2)
    key = (workload.cache_key(), plat, n_pad, structured)
    if key not in _CACHE:
        spec = GenomeSpec(workload, arch=plat)
        _CACHE[key] = (spec, JaxCostModel(spec, plat, n_pad=n_pad,
                                          structured=structured or None))
    return _CACHE[key]


def clear_cache() -> None:
    """Drop cached evaluators AND the shared jitted kernels (benchmark
    hook for counting compilations from a cold start)."""
    _CACHE.clear()
    jax_cost.clear_compile_cache()


def run(method: str, workload: Workload,
        platform: PlatformLike, budget: int = 20_000,
        seed: int = 0, **kw) -> SearchResult:
    if method not in METHODS:
        raise KeyError(f"unknown method {method!r}; have {list(METHODS)}")
    plat = _platform(platform)
    spec, ev = get_evaluator(workload, plat)
    res = METHODS[method](spec, ev, budget, seed, plat, **kw)
    res.extras.setdefault("arch", plat)
    return res


def decode_best(workload: Workload, result: SearchResult,
                platform: Optional[PlatformLike] = None) -> Optional[Design]:
    """Decode a result's best genome.  ``platform`` selects the arch the
    search ran on; when omitted, the arch recorded in the result's extras
    is used (falling back to the paper topology for results that predate
    the recording).  Any same-topology description works."""
    if result.best_genome is None:
        return None
    if platform is None:
        platform = result.extras.get("arch")
    spec = GenomeSpec(workload) if platform is None else \
        GenomeSpec(workload, arch=_platform(platform))
    return spec.decode(result.best_genome)


def report_best(workload: Workload, platform: PlatformLike,
                result: SearchResult) -> Optional[CostReport]:
    plat = _platform(platform)
    d = decode_best(workload, result, platform=plat)
    if d is None:
        return None
    return evaluate(d, plat)


# ---------------------------------------------------------------- multi


#: per-backend default for ``MultiSearch(device_rounds=None)``.  CPU stays
#: at 1 — measured in the PR 6 baseline: folded scans win on host syncs
#: but XLA:CPU's scan program loses wall-clock to the per-round path, so
#: folding is opt-in there.  Accelerator backends amortize the scan
#: compile over k dispatch-free generations; 4 (gpu) / 8 (tpu) follow the
#: ROADMAP sizing note (larger k = fewer host syncs but longer-horizon
#: stale budgets, so segments overshoot budget boundaries by up to k-1
#: generations of padding work).
_DEFAULT_DEVICE_ROUNDS = {"cpu": 1, "gpu": 4, "tpu": 8}


def default_device_rounds(backend: Optional[str] = None) -> int:
    """The fleet ``device_rounds`` default for a JAX backend (the running
    ``jax.default_backend()`` when not given).  Unknown backends fall
    back to 1 — the always-correct per-round path."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    return _DEFAULT_DEVICE_ROUNDS.get(backend, 1)


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """The fleet runtime configuration — every knob
    :class:`MultiSearch` accepts, in one validated, frozen, serializable
    object (``MultiSearch(tasks, FleetConfig(...))``).  This replaces the
    accreted ``MultiSearch.__init__`` kwargs (still accepted as
    deprecated aliases) and doubles as the sweep server's wire schema:
    ``to_json()``/``from_json()`` round-trip everything except ``mesh``,
    which is a process-local ``jax.sharding.Mesh`` and must be rebuilt on
    the serving side.

    ``device_rounds=None`` defers to the per-backend default
    (:func:`default_device_rounds`); :meth:`resolved_device_rounds`
    resolves it in exactly one place and reports the provenance string
    the fleet ``stats`` record.

    ``stack_batches`` is a validated constant: the fleet always
    mega-batches per signature, and ``False`` is refused.  The field
    stays only so that configurations which spell out
    ``"stack_batches": true`` still load."""

    align_signatures: bool = True
    stack_batches: bool = True
    device_rounds: Optional[int] = None
    mesh: object = None
    device_execute: bool = True
    pipeline: bool = True
    compile_ahead: bool = True

    def __post_init__(self):
        for flag in ("align_signatures", "stack_batches",
                     "device_execute", "pipeline", "compile_ahead"):
            object.__setattr__(self, flag, bool(getattr(self, flag)))
        if not self.stack_batches:
            raise ValueError(
                "stack_batches=False is not supported: the per-task "
                "dispatch path was removed, and every fleet mega-batches "
                "its same-signature tasks")
        if self.device_rounds is not None:
            if int(self.device_rounds) < 1:
                raise ValueError("device_rounds must be >= 1")
            object.__setattr__(self, "device_rounds",
                               int(self.device_rounds))

    def resolved_device_rounds(self) -> Tuple[int, str]:
        """``(value, provenance)``: the explicit value, or the
        per-backend default (CPU=1, documented at
        ``_DEFAULT_DEVICE_ROUNDS``) tagged ``"default:<backend>"``."""
        if self.device_rounds is None:
            import jax
            backend = jax.default_backend()
            return default_device_rounds(backend), f"default:{backend}"
        return self.device_rounds, "explicit"

    def to_json_dict(self) -> Dict:
        if self.mesh is not None:
            raise ValueError(
                "FleetConfig.mesh is process-local (a jax Mesh) and "
                "cannot be serialized; rebuild the mesh on the serving "
                "side and attach it there")
        return dict(
            version=1,
            align_signatures=self.align_signatures,
            device_rounds=self.device_rounds,
            device_execute=self.device_execute,
            pipeline=self.pipeline,
            compile_ahead=self.compile_ahead)

    def to_json(self) -> str:
        return json.dumps(self.to_json_dict(), sort_keys=True)

    @classmethod
    def from_json(cls, data: Union[str, Dict]) -> "FleetConfig":
        d = dict(json.loads(data) if isinstance(data, str) else data)
        version = d.pop("version", 1)
        if version != 1:
            raise ValueError(f"unknown FleetConfig schema version "
                             f"{version!r}")
        unknown = set(d) - {f.name for f in dataclasses.fields(cls)}
        if unknown:
            raise ValueError(f"unknown FleetConfig fields: "
                             f"{sorted(unknown)}")
        return cls(**d)


#: sentinel distinguishing "kwarg not passed" from any real value in the
#: deprecated MultiSearch keyword aliases
_UNSET = object()


@dataclasses.dataclass
class SearchTask:
    """One (method, workload, platform) search in a :class:`MultiSearch`
    fleet.  ``method`` must have a request generator
    (``baselines.REQUEST_METHODS``); ``method_kw`` is forwarded to its
    factory.  ``es_kw`` is the deprecated pre-method-agnostic alias —
    still merged (``method_kw`` wins on conflicts) but it warns.

    ``runtime_kw`` carries process-local factory extras the wire schema
    must not see — warm-start ``seeds`` rows, ``resume_state`` /
    ``state_out`` checkpoint hooks (the sweep server's durability path).
    It is excluded from ``to_json()`` and from the compile-ahead
    predictors.
    """
    workload: Workload
    platform: PlatformLike = "cloud"
    budget: int = 20_000
    seed: int = 0
    name: Optional[str] = None
    method: str = "sparsemap"
    method_kw: Dict = dataclasses.field(default_factory=dict)
    es_kw: Dict = dataclasses.field(default_factory=dict)
    runtime_kw: Dict = dataclasses.field(default_factory=dict,
                                         repr=False, compare=False)

    def __post_init__(self):
        if self.method not in REQUEST_METHODS:
            raise KeyError(
                f"method {self.method!r} has no request generator; "
                f"have {sorted(REQUEST_METHODS)}")
        if self.es_kw:
            warnings.warn(
                "SearchTask.es_kw is deprecated; pass method_kw=... "
                "(merge semantics preserved: method_kw wins)",
                DeprecationWarning, stacklevel=3)
            self.method_kw = {**self.es_kw, **self.method_kw}

    def resolved_name(self) -> str:
        if self.name:
            return self.name
        base = f"{self.workload.name}@{_platform(self.platform).name}"
        return base if self.method == "sparsemap" else \
            f"{self.method}:{base}"

    def to_json_dict(self) -> Dict:
        """JSON-able wire form: the workload by its ``cache_key`` fields
        (density models via registered family names), the platform by
        registry name, and the method's factory kwargs.  ``runtime_kw``
        (process-local) and ``es_kw`` (already merged) are excluded —
        a server query is exactly this dict plus a FleetConfig
        fragment."""
        return dict(
            version=1,
            workload=workload_to_dict(self.workload),
            platform=_platform(self.platform).name,
            budget=int(self.budget),
            seed=int(self.seed),
            name=self.name,
            method=self.method,
            method_kw=dict(self.method_kw))

    def to_json(self) -> str:
        return json.dumps(self.to_json_dict(), sort_keys=True)

    @classmethod
    def from_json(cls, data: Union[str, Dict]) -> "SearchTask":
        d = dict(json.loads(data) if isinstance(data, str) else data)
        version = d.pop("version", 1)
        if version != 1:
            raise ValueError(f"unknown SearchTask schema version "
                             f"{version!r}")
        unknown = set(d) - {"workload", "platform", "budget", "seed",
                            "name", "method", "method_kw"}
        if unknown:
            raise ValueError(f"unknown SearchTask fields: "
                             f"{sorted(unknown)}")
        return cls(
            workload=workload_from_dict(d["workload"]),
            platform=d.get("platform", "cloud"),
            budget=int(d.get("budget", 20_000)),
            seed=int(d.get("seed", 0)),
            name=d.get("name"),
            method=d.get("method", "sparsemap"),
            method_kw=dict(d.get("method_kw") or {}))


@dataclasses.dataclass
class _TaskState:
    name: str
    gen: object                      # the method's request generator
    tracker: _Budget
    ev: JaxCostModel
    natural: Tuple[int, int]         # (ndims, natural prime bucket)
    method: str
    req: Optional[np.ndarray] = None
    extras: Optional[Dict] = None
    phase: str = ""                  # the tracker's phase last seen
    # signature + segment shape of the scans it will run (None: none)
    scan_key: Optional[Tuple] = None

    @property
    def signature(self) -> Tuple[int, int, str]:
        return self.ev.signature


#: The mega-batch pad watermark's decay rule (:meth:`MultiSearch.step`):
#: decay after this many quiet rounds, and to a shape the fleet has not
#: run only when each quiet round needs at most this share of it.  These
#: are the values every registered topology derived from the CPU
#: baseline's round-1-spike watermark trajectory (a spike that decays
#: once and never re-grows); a fleet decays to a shape it has already
#: run whatever the ratio, so they decide only decays to new shapes.
PAD_DECAY_ROUNDS = 2
PAD_DECAY_RATIO = 0.125

#: recorder counter of watermark decays; ``warm=True`` marks one the
#: :data:`PAD_DECAY_RATIO` test refused, taken because the fleet had run
#: the shape
PAD_DECAYS = "fleet.pad_decays"


class MultiSearch:
    """Run a fleet of (method, workload, platform) searches concurrently.

    Each task's engine is a request generator (``evolve_requests`` for
    SparseMap populations, ``baselines.*_requests`` for the baseline
    optimizers); every round, each pending task's next batch is evaluated
    and the generator advanced, with tasks ordered by compilation
    signature.  Every round concatenates all same-signature pending
    batches into ONE padded mega-batch and issues a single device
    dispatch per signature (``jax_cost.eval_stacked``), slicing the
    results back per task; the baselines' odd native batch sizes (48,
    50, 64) simply become rows of the shared power-of-two-padded
    mega-batch.

    With ``align_signatures=True`` (default), each workload's prime axis
    is padded up to the largest bucket among its same-ndims peers,
    collapsing the group onto one (ndims, bucket) signature — a sweep over
    the paper's workload table then reuses compilations instead of paying
    XLA tracing per workload (the padding primes are 1.0 and numerically
    inert).

    With ``device_rounds=k > 1``, tasks whose method is scan-foldable
    (``baselines.SEGMENT_METHODS``) advance in k-generation device
    segments: the generator yields a :class:`~repro.core.es_ops.
    DeviceSegment` carrying the pre-drawn per-generation operator plans,
    the driver runs {select -> crossover -> mutate -> cost} for all k
    generations as ONE ``lax.scan`` program (``jax_cost.run_segments``,
    same-signature same-shape segments stacked and, with ``mesh``,
    sharded across devices), and the host syncs only once per segment for
    ``_Budget`` accounting and history.  ``standard_es`` folds too — its
    direct-to-canonical translation runs in-scan (``kind="direct"``
    segments) — as does ``stagnation_restart > 0`` (a re-init branch on
    the carried best-so-far).  Methods without a device path
    (PSO/MCTS/PPO/DQN, ``random_mapper``) keep the per-round path
    transparently, and mixed fleets interleave both.
    ``device_rounds=None`` (the default) resolves per backend via
    :func:`default_device_rounds` (CPU=1); ``stats`` record the resolved
    value and its provenance (``device_rounds_source``).
    ``device_execute=False`` forces the host-loop reference path: the
    driver answers each segment with ``None`` and the generator replays
    the identical operator plan per-round on the host (bit-identical
    trajectories; see COMPAT.md "Device-resident round protocol").

    With ``pipeline=True`` (default) the round loop is software-
    pipelined: segment results come back deferred and are resolved one
    round late by the request generators, and stacked mega-batches are
    dispatched for ALL signature groups before any is finalized — JAX
    async dispatch overlaps the host's numpy conversions with device
    execution.  ``pipeline=False`` is the escape hatch and is
    bit-identical by construction (same dispatches, same registration
    order, merely blocking earlier); ``stats["host_blocked_s"]`` records
    the host time actually spent blocked on conversions either way.

    With ``compile_ahead=True`` (default) the fleet's round-1 dispatch
    shapes (plus each topology's committed pad-watermark shapes and the
    segment scan programs) are predicted from the task list and AOT-
    compiled on a background thread while the host runs the HSHI/LHS
    prologue; ``stats["compile_ahead_hits"/"compile_ahead_misses"]``
    report registry coverage next to ``jax_cost.compilation_count()``.

    After :meth:`run`, ``stats`` holds the weighted round count, host
    sync count, device-dispatch count, and the aligned and natural
    signature sets.  Duplicate resolved task names are made explicit:
    every colliding name gets a ``#k`` suffix (``name#0``, ``name#1``,
    ...), so no two tasks ever silently share a results key.
    """

    def __init__(self, tasks: Iterable,
                 config: Optional[FleetConfig] = None, *,
                 align_signatures=_UNSET, device_rounds=_UNSET, mesh=_UNSET,
                 device_execute=_UNSET, pipeline=_UNSET,
                 compile_ahead=_UNSET):
        norm: List[SearchTask] = []
        for t in tasks:
            norm.append(self._as_task(t))
        if not norm:
            raise ValueError("MultiSearch needs at least one task")
        legacy = {k: v for k, v in dict(
            align_signatures=align_signatures,
            device_rounds=device_rounds, mesh=mesh,
            device_execute=device_execute, pipeline=pipeline,
            compile_ahead=compile_ahead).items() if v is not _UNSET}
        if legacy:
            if config is not None:
                raise ValueError(
                    f"pass config=FleetConfig(...) OR the legacy "
                    f"kwargs, not both (got config and "
                    f"{sorted(legacy)})")
            warnings.warn(
                f"MultiSearch({', '.join(sorted(legacy))}=...) keyword "
                f"configuration is deprecated; pass "
                f"config=FleetConfig(...)", DeprecationWarning,
                stacklevel=2)
            config = FleetConfig(**legacy)
        if config is None:
            config = FleetConfig()
        self.tasks = norm
        self.config = config
        # resolved views (one resolution point: FleetConfig)
        self.align_signatures = config.align_signatures
        self.device_rounds, self.device_rounds_source = \
            config.resolved_device_rounds()
        self.mesh = config.mesh
        self.device_execute = config.device_execute
        self.pipeline = config.pipeline
        self.compile_ahead = config.compile_ahead
        self.final_names: List[str] = self._resolve_names(norm)
        self.stats: Dict = {}
        # ids the caller gives task names (the sweep server's query ids);
        # each task's ``es.phase`` trace marks carry its id
        self.trace_ids: Dict[str, object] = {}
        self._started = False
        # set by run(): no task joins later, so a scan group's size at
        # the start is the most tasks its dispatches can hold
        self._fixed_tasks = False

    @staticmethod
    def _as_task(t) -> SearchTask:
        if isinstance(t, SearchTask):
            return t
        if isinstance(t, Workload):
            return SearchTask(t)
        return SearchTask(*t)

    @staticmethod
    def _resolve_names(tasks: Sequence[SearchTask]) -> List[str]:
        base = [t.resolved_name() for t in tasks]
        dup = {n for n, c in Counter(base).items() if c > 1}
        taken = set(base)       # every base name reserves its spot
        next_k: Dict[str, int] = {}
        names = []
        for n in base:
            if n not in dup:
                names.append(n)
                continue
            k = next_k.get(n, 0)
            while f"{n}#{k}" in taken:  # don't collide with explicit names
                k += 1
            next_k[n] = k + 1
            taken.add(f"{n}#{k}")
            names.append(f"{n}#{k}")
        return names

    def _compile_ahead_jobs(self, infos: List[Tuple]) -> List[Tuple]:
        """The AOT (key, jit_fn, arg_structs) jobs predicted from the
        fleet's tasks: round-1 mega-batch shapes per signature group, the
        baseline's pad-watermark shapes of each topology (the steady-state
        mega-batch sizes a committed baseline measured), and the scan /
        direct-scan programs of segment-foldable tasks at every task-slot
        bucket (``jax_cost.scan_slots``) up to each group's size; a
        running fleet queues more as admissions grow a group
        (:meth:`admit`).  Predictions are
        conservative: a signature group whose round-1 rows cannot all be
        predicted contributes NO job (its family stays unclaimed, so jit
        fallbacks there never count as compile-ahead misses)."""
        from .baselines import round1_rows, steady_rows
        # the worker compiles in list order and a racing dispatch WAITS
        # for its queued key, so order jobs by when the fleet needs
        # them: round-1 shapes first, segment scans next (needed right
        # after the prologue), steady-state watermark extras last
        jobs: List[Tuple] = []
        late: List[Tuple] = []
        seen: set = set()

        def add(job: Tuple, when: List[Tuple] = jobs) -> None:
            if job[0] not in seen:
                seen.add(job[0])
                when.append(job)

        def watermarks(topology_fingerprint: str) -> List[int]:
            try:
                from repro.configs.archs import measured_watermark_values
            except ImportError:         # pragma: no cover - jax-less
                return []
            return measured_watermark_values(topology_fingerprint)

        rows: List[Optional[int]] = []
        for task, kw, spec, ev in infos:
            try:
                rows.append(round1_rows(task.method, spec, task.budget,
                                        task.seed, **kw))
            except (TypeError, ValueError):
                rows.append(None)
        by_sig: Dict[Tuple, List[int]] = {}
        for i, (task, kw, spec, ev) in enumerate(infos):
            by_sig.setdefault(ev.signature, []).append(i)
        for sig in sorted(by_sig):
            idx = by_sig[sig]
            model = infos[idx[0]][3]
            # the group's per-task constants table: one slot count
            slots = jax_cost.table_slots(
                sig, len({infos[i][3]._table_key for i in idx}))
            if all(rows[i] is not None for i in idx):
                total = sum(rows[i] for i in idx)
                add(jax_cost.stacked_compile_job(
                    model, jax_cost._pad_batch(total), slots))
                # decayed steady-state shapes: once round-1 shapes
                # (calibration / first chunks) age out of the pad
                # watermark, the mega-batch settles on the sum of the
                # survivors' per-round batches
                steads = []
                for i in idx:
                    task, kw = infos[i][0], infos[i][1]
                    try:
                        steads.append(steady_rows(
                            task.method, infos[i][2], task.budget,
                            task.seed, **kw))
                    except (TypeError, ValueError):
                        steads.append(None)
                if all(s is not None for s in steads):
                    alive = [s for s in steads if s]
                    for tot in sorted({sum(s[0] for s in alive),
                                       sum(s[-1] for s in alive)}):
                        if tot > 0:
                            add(jax_cost.stacked_compile_job(
                                model, jax_cost._pad_batch(tot), slots),
                                when=late)
                for v in watermarks(sig[2]):
                    add(jax_cost.stacked_compile_job(model, int(v), slots),
                        when=late)
        # every task-slot bucket the group's scans can run at, so that
        # no count of tasks the traffic leaves compiles at dispatch
        seg_groups: Dict[Tuple, List[Tuple]] = {}
        for task, kw, spec, ev in infos:
            key = self._scan_key(task, kw, spec, ev)
            if key is not None:
                seg_groups.setdefault(key, []).append(ev)
        for key in sorted(seg_groups, key=repr):
            n = len(seg_groups[key])
            cap = n if self._fixed_tasks else None
            for slots in sorted({jax_cost.scan_slots(t, cap)
                                 for t in range(1, n + 1)}):
                add(self._scan_job(key, seg_groups[key][0], slots))
        return jobs + late

    def _scan_key(self, task: SearchTask, kw: Dict, spec,
                  ev) -> Optional[Tuple]:
        """The signature + segment shape (``es_ops.segment_shape_key``'s
        fields) of the scans a task will dispatch, or None when it will
        dispatch none."""
        from .baselines import segment_plan
        if not self.device_execute:
            return None
        plan = segment_plan(task.method, spec, task.budget, task.seed,
                            **kw)
        if plan is None:
            return None
        return ev.signature + tuple(plan[f] for f in (
            "B", "rounds", "n_parents", "n_elite", "genes_per", "kind",
            "restart"))

    @staticmethod
    def _scan_job(key: Tuple, ev, slots: int) -> Tuple:
        """The AOT job of one scan group's program at ``slots`` tasks."""
        B, k, n_parents, n_elite, genes_per, kind, restart = key[4:]
        if kind == "direct":
            from .direct_encoding import DirectValueSpec
            dspec = DirectValueSpec(ev.spec)
            return jax_cost.direct_scan_compile_job(
                ev, B, k, n_parents, n_elite, genes_per, slots,
                dspec.length, dspec.n_perm_codes)
        return jax_cost.scan_compile_job(ev, B, k, n_parents, n_elite,
                                         genes_per, slots, restart=restart)

    def _advance(self, st: _TaskState, out: Dict) -> bool:
        """Send an evaluation to a task's generator; False when done.
        A change of the search's phase (``_Budget.phase``: calibration,
        init, main) is marked in the trace as ``es.phase``."""
        try:
            st.req = st.gen.send(out)
            alive = True
        except StopIteration as stop:
            st.extras = stop.value or {}
            alive = False
        phase = st.tracker.phase
        if phase != st.phase:
            st.phase = phase
            t = time.perf_counter()
            trace.record("es.phase", t, t, task=st.name, phase=phase,
                         query=self.trace_ids.get(st.name))
        return alive

    def _task_infos(self) -> List[Tuple]:
        """One signature-aligned (task, method_kw, spec, evaluator)
        tuple per task — the prediction inputs
        :meth:`_compile_ahead_jobs` consumes.  Builds evaluators but
        starts no request generator, so tests and tooling can inspect
        the fleet's predicted AOT jobs without running a round."""
        naturals = [(t.workload.ndims,
                     _bucket(max(len(t.workload.prime_factors), 1)))
                    for t in self.tasks]
        pad_for: Dict[int, int] = {}
        # density-mode alignment, same spirit as prime-axis padding: if
        # any same-ndims peer declares a structured density model, the
        # whole group runs on the structured kernel (uniform members'
        # models become traced family rows), so a mixed
        # uniform/banded/N:M fleet still shares one signature — one
        # mega-batch dispatch per round
        structured_for: Dict[int, bool] = {}
        if self.align_signatures:
            for (d, bucket), t in zip(naturals, self.tasks):
                pad_for[d] = max(pad_for.get(d, 0), bucket)
                structured_for[d] = structured_for.get(d, False) or \
                    t.workload.structured_density
        # kept for mid-run admission: a task admitted later aligns UP to
        # the group's current bucket/density mode (never re-padding the
        # already-compiled incumbents)
        self._pad_for = pad_for
        self._structured_for = structured_for

        infos: List[Tuple] = []
        for task, natural in zip(self.tasks, naturals):
            plat = _platform(task.platform)
            n_pad = pad_for.get(natural[0]) if self.align_signatures \
                else None
            if n_pad == natural[1]:
                n_pad = None        # natural bucket: share the plain entry
            spec, ev = get_evaluator(
                task.workload, plat, n_pad=n_pad,
                structured=structured_for.get(natural[0], False))
            kw = dict(task.method_kw)
            if self.device_rounds > 1 and task.method in SEGMENT_METHODS:
                # scan-foldable engines fold k generations per segment;
                # an explicit per-task device_rounds wins over the fleet's
                kw.setdefault("device_rounds", self.device_rounds)
            infos.append((task, kw, spec, ev))
        return infos

    def start(self) -> None:
        """Build evaluators, queue compile-ahead jobs, and prime every
        task's request generator — the fleet is then live and
        :meth:`step` advances it one driver iteration at a time.
        Idempotent; :meth:`run` is ``start(); while step(): pass;
        finish()`` and is bit-identical to the pre-incremental driver."""
        if self._started:
            return
        self._started = True
        infos = self._task_infos()
        states: List[_TaskState] = []
        for (task, kw, spec, ev), name in zip(infos, self.final_names):
            gen, tracker = make_requests(task.method, spec,
                                         _platform(task.platform),
                                         task.budget, task.seed,
                                         **{**kw, **task.runtime_kw})
            states.append(_TaskState(
                name=name, gen=gen, tracker=tracker, ev=ev,
                natural=(task.workload.ndims,
                         _bucket(max(len(task.workload.prime_factors),
                                     1))),
                method=task.method,
                scan_key=self._scan_key(task, kw, spec, ev)))

        self._ca0 = jax_cost.compile_ahead_counts()
        self._ca_errors0 = jax_cost.compile_ahead_errors()[0]
        self._blocked0 = jax_cost.host_blocked_s()
        # AOT-compile the predicted round-1 + watermark + scan shapes on a
        # background thread NOW — the compile spike overlaps the host-side
        # HSHI/LHS/calibration prologue instead of serializing with the
        # first dispatch of each shape.  Called with no jobs too: every
        # fleet retires the previous fleet's worker and claims its own
        # families (none when compile-ahead is off)
        self._ca_jobs = self._compile_ahead_jobs(infos) \
            if self.compile_ahead else []
        jax_cost.compile_ahead(self._ca_jobs)
        self._scan_caps = Counter(st.scan_key for st in states
                                  if st.scan_key is not None) \
            if self._fixed_tasks else {}

        # group same-signature tasks so they share warm compilations (and,
        # when stacking, one mega-batch); stable within a signature
        states.sort(key=lambda s: s.signature)
        self._states = states
        self._alive: List[_TaskState] = []
        self._done: List[str] = []
        for st in states:
            try:
                st.req = next(st.gen)
                self._alive.append(st)
            except StopIteration as stop:
                st.extras = stop.value or {}
                self._done.append(st.name)
            st.phase = st.tracker.phase
        self._pad_hwm: Dict[Tuple[int, int, str], int] = {}
        self._pad_recent: Dict[Tuple[int, int, str],
                               List[Tuple[int, int]]] = {}
        # padded mega-batch rows this fleet has dispatched, per signature
        self._pad_run: Dict[Tuple[int, int, str], set] = {}
        # the watermark as growth and ratio decays alone would hold it
        self._pad_ref: Dict[Tuple[int, int, str], int] = {}
        self._pad_decays = {"warm": 0, "cold": 0}
        self._wm_hist: Dict[Tuple[int, int, str], List[int]] = {}
        self._rounds = 0     # weighted generation clock (k per segment)
        self._host_syncs = 0   # driver loop iterations (host roundtrips)
        self._seg_syncs = 0    # iterations that device-advanced segments
        self._seg_rounds = 0   # generation rounds covered by those
        self._dispatch0 = jax_cost.dispatch_count()

    def admit(self, task, name: Optional[str] = None) -> str:
        """Admit one more task into the RUNNING fleet (the sweep
        server's entry point: one more user query costs rows in an
        already-dispatched mega-batch, not a new fleet).  The newcomer
        aligns UP to its signature group's current prime bucket and
        density mode — incumbents are never re-padded, so their warm
        compilations survive — and joins the group's mega-batch on the
        next :meth:`step`.  Returns the resolved (collision-suffixed)
        task name.  Compile-ahead prediction covers the starting fleet,
        and the scan programs at each task-slot bucket an admission lets
        a segment group reach; an admitted task with a novel signature
        jit-compiles its mega-batch shapes on first dispatch."""
        task = self._as_task(task)
        self.start()
        wl = task.workload
        d = wl.ndims
        bucket = _bucket(max(len(wl.prime_factors), 1))
        n_pad = None
        structured = False
        if self.align_signatures:
            self._pad_for[d] = max(self._pad_for.get(d, 0), bucket)
            self._structured_for[d] = \
                self._structured_for.get(d, False) or \
                wl.structured_density
            n_pad = self._pad_for[d]
            structured = self._structured_for[d]
            if n_pad == bucket:
                n_pad = None
        plat = _platform(task.platform)
        spec, ev = get_evaluator(wl, plat, n_pad=n_pad,
                                 structured=structured)
        kw = dict(task.method_kw)
        if self.device_rounds > 1 and task.method in SEGMENT_METHODS:
            kw.setdefault("device_rounds", self.device_rounds)
        base = name or task.resolved_name()
        resolved, k = base, 0
        while resolved in self.final_names:
            resolved = f"{base}#{k}"
            k += 1
        gen, tracker = make_requests(task.method, spec, plat,
                                     task.budget, task.seed,
                                     **{**kw, **task.runtime_kw})
        st = _TaskState(name=resolved, gen=gen, tracker=tracker, ev=ev,
                        natural=(d, bucket), method=task.method,
                        scan_key=self._scan_key(task, kw, spec, ev))
        self.tasks.append(task)
        self.final_names.append(resolved)
        self._states.append(st)
        try:
            st.req = next(st.gen)
            self._alive.append(st)
        except StopIteration as stop:
            st.extras = stop.value or {}
            self._done.append(st.name)
        st.phase = st.tracker.phase
        if self.compile_ahead and st.scan_key is not None:
            self._compile_scan_slots(st)
        return resolved

    def _compile_scan_slots(self, st: _TaskState) -> None:
        """Queue the scan programs an admission lets the newcomer's
        group reach (every bucket up to its live task count) that the
        fleet has not queued yet, beside the jobs still owed."""
        live = sum(1 for s in self._alive if s.scan_key == st.scan_key)
        have = {job[0] for job in self._ca_jobs}
        new = [job for job in (
            self._scan_job(st.scan_key, st.ev, slots)
            for slots in sorted({jax_cost.scan_slots(t)
                                 for t in range(1, live + 1)}))
            if job[0] not in have]
        if new:
            # first in the queue: the newcomer needs them after its
            # prologue, sooner than any steady-state shape still owed
            self._ca_jobs = new + self._ca_jobs
            jax_cost.compile_ahead(self._ca_jobs)

    @property
    def done(self) -> bool:
        """True once every task (initial + admitted) has retired."""
        return self._started and not self._alive

    def pop_done(self) -> List[Tuple[str, SearchResult]]:
        """Drain the retirement queue: ``(name, result)`` for every task
        that finished since the last call (the server streams these to
        their clients and feeds the warm-start library)."""
        out = [(n, self.result_of(n)) for n in self._done]
        self._done = []
        return out

    def result_of(self, name: str) -> SearchResult:
        """The (possibly in-flight) result of one task by resolved
        name — retired tasks get their final result, live tasks a
        best-so-far snapshot."""
        for st in self._states:
            if st.name == name:
                return self._result_for(st)
        raise KeyError(f"no task named {name!r}; have "
                       f"{self.final_names}")

    def step(self) -> bool:
        """One driver iteration: advance segmented tasks by k
        generations and per-round tasks by 1 (mega-batched per
        signature).  Retired tasks land in the :meth:`pop_done` queue.
        Returns True while any task is still alive.

        The pad floor (mega-batch watermark) grows to the largest padded
        round immediately (shrinking fleets keep hitting the warm
        shape), and decays to the recent maximum after
        :data:`PAD_DECAY_ROUNDS` quiet rounds.  When this fleet has
        already dispatched that shape (it keeps the padded rows of each
        of its mega-batches per signature) the decay costs no trace and
        is taken at once, so a spike from an admitted query's
        calibration batch does not hold later rounds at its shape.  A
        shape not yet run is decayed to only when each quiet round needs
        at most :data:`PAD_DECAY_RATIO` of the watermark as growth and
        these cold decays set it (``_pad_ref``;
        a warm decay to a middle shape must not shut out a smaller cold
        one the ratio allows) — one extra XLA trace instead of paying
        mostly-padding kernel compute every round after a one-off spike
        (e.g. round-1 calibration probes + random_mapper's 512-row
        chunks).  Each decay counts in the recorder's
        :data:`PAD_DECAYS` and in ``stats["pad_decays"]``.  The
        per-round watermark trajectory lands in
        ``stats["pad_watermarks"]`` for cross-PR tracking.  The
        ``pad_recent`` observations are (target, weight) pairs; weight =
        search rounds the fleet clock advanced at that observation, so
        quiet-round decay scales with device-segment length (one host
        observation per k rounds must count as k quiet rounds, not 1 —
        otherwise a post-spike watermark never decays under segmented
        fleets)."""
        self.start()
        alive = self._alive
        if not alive:
            return False
        pad_hwm = self._pad_hwm
        pad_recent = self._pad_recent
        pad_run = self._pad_run
        pad_ref = self._pad_ref
        wm_hist = self._wm_hist
        pending: List[_TaskState] = []
        seg_states = [st for st in alive
                      if isinstance(st.req, DeviceSegment)]
        plain = [st for st in alive
                 if not isinstance(st.req, DeviceSegment)]
        # one iteration advances segmented tasks by k generations and
        # per-round tasks by 1; the fleet's round clock moves by the
        # largest stride taken this iteration
        seg_groups: Dict[Tuple, List[_TaskState]] = {}
        if self.device_execute:
            for st in seg_states:
                key = st.signature + es_ops.segment_shape_key(st.req)
                seg_groups.setdefault(key, []).append(st)
        iter_weight = max((grp[0].req.rounds
                           for grp in seg_groups.values()), default=0)
        if plain:
            iter_weight = max(iter_weight, 1)
        dispatched: List[Tuple[List[_TaskState], object]] = []
        groups: Dict[Tuple[int, int, str], List[_TaskState]] = {}
        for st in plain:
            groups.setdefault(st.signature, []).append(st)
        # two-phase round: FIRST enqueue every signature group's
        # mega-batch (with pipeline=True the dispatches return
        # StackedPending handles, so all groups' device work is in
        # flight together), THEN finalize + advance in the same sorted
        # order — round N's host-blocking conversion of group i overlaps
        # groups i+1..n computing.  The watermark bookkeeping is
        # value-independent (row counts are known at dispatch), so it
        # stays in dispatch order and pipeline on/off cannot change any
        # padded shape.  The mega-batches go to the device ahead of this
        # step's scans: the step waits for them (finalize below), and a
        # scan queued in front would hold them back by its whole run,
        # where the next step harvests the scan's results
        for sig in sorted(groups):
            grp = groups[sig]
            hwm = pad_hwm.get(sig, 0)
            outs = jax_cost.eval_stacked(
                [s.ev for s in grp], [s.req for s in grp],
                pad_floor=hwm, mesh=self.mesh,
                defer=self.pipeline)
            dispatched.append((grp, outs))
            total = sum(len(s.req) for s in grp)
            target = jax_cost._pad_batch(total)
            ran = pad_run.setdefault(sig, set())
            ran.add(jax_cost.stacked_rows(total, hwm, self.mesh))
            hist = pad_recent.setdefault(sig, [])
            hist.append((target, max(iter_weight, 1)))
            wtot = sum(w for _, w in hist)
            while hist and wtot - hist[0][1] >= PAD_DECAY_ROUNDS:
                wtot -= hist.pop(0)[1]
            ref = pad_ref.get(sig, 0)
            if target > hwm:
                pad_hwm[sig] = target
                pad_ref[sig] = max(ref, target)
                hist.clear()
            elif wtot >= PAD_DECAY_ROUNDS:
                peak = max(t for t, _ in hist)
                # the ratio is taken against the watermark warm decays
                # leave alone, so that a warm decay to a middle shape
                # never shuts out a smaller cold one
                cold = all(t <= ref * PAD_DECAY_RATIO for t, _ in hist)
                warm = peak < hwm and jax_cost.stacked_rows(
                    peak, mesh=self.mesh) in ran
                if cold or warm:
                    pad_hwm[sig] = peak
                    if cold:
                        pad_ref[sig] = peak
                    hist.clear()
                    if peak < hwm:
                        self._pad_decays["cold" if cold else "warm"] += 1
                        trace.count(PAD_DECAYS, sig=sig, warm=not cold)
            wm_hist.setdefault(sig, []).append(pad_hwm[sig])
        for key in sorted(seg_groups):
            grp = seg_groups[key]
            # with pipeline=True the SegmentResults come back
            # unresolved (defer): the generators stash them, yield
            # the NEXT segment from the device-resident carry, and
            # only then resolve round N — the blocking conversion
            # overlaps round N+1's device execution (COMPAT.md
            # "Pipelined dispatch contract")
            segres = jax_cost.run_segments(
                [s.ev for s in grp], [s.req for s in grp],
                mesh=self.mesh, defer=self.pipeline,
                cap=self._scan_caps.get(key))
            # the generators resolve the previous segment's harvest
            # in here: its fleet.block span nests in this one
            with trace.span("fleet.advance", step=self._host_syncs,
                            sig=key[:4]):
                for st, res in zip(grp, segres):
                    if self._advance(st, res):
                        pending.append(st)
        if seg_states and not self.device_execute:
            # host-loop reference path: the generator replays the
            # identical pre-drawn plan per-round (its next yield is a
            # plain batch, so the task rejoins the per-round path)
            with trace.span("fleet.advance", step=self._host_syncs):
                for st in seg_states:
                    if self._advance(st, None):
                        pending.append(st)
        if seg_groups:
            self._seg_syncs += 1
            self._seg_rounds += iter_weight
        for grp, outs in dispatched:
            # from the group's results to its next batches: the
            # finalize's fleet.block span nests in this one
            with trace.span("fleet.advance", step=self._host_syncs,
                            sig=grp[0].signature):
                if isinstance(outs, jax_cost.StackedPending):
                    outs = outs.finalize()
                for st, out in zip(grp, outs):
                    if self._advance(st, out):
                        pending.append(st)
        live = {id(st) for st in pending}
        for st in alive:
            if id(st) not in live:
                self._done.append(st.name)
        self._alive = pending
        self._rounds += iter_weight
        self._host_syncs += 1
        return bool(self._alive)

    @staticmethod
    def _result_for(st: _TaskState) -> SearchResult:
        extras = dict(st.extras or {})
        extras["signature"] = st.signature
        extras["natural_signature"] = st.natural
        extras.setdefault("method", st.method)
        extras.setdefault("arch", st.ev.arch)
        return SearchResult(
            best_edp=st.tracker.best,
            best_genome=st.tracker.best_genome,
            history=np.asarray(st.tracker.hist),
            evals=st.tracker.evals,
            valid_evals=st.tracker.valid,
            extras=extras)

    def stats_snapshot(self) -> Dict:
        """The fleet stats as of now — same shape as the final
        ``stats``, computable mid-run (the server's ``stats`` op)."""
        self.start()
        # host_syncs_per_round: 1.0 for per-round fleets; for segmented
        # fleets the steady-state metric is over the segment phase (the
        # HSHI/calibration prologue is inherently host-driven, so the
        # whole-run ratio can never reach 1/k) — seg iterations each
        # cover k generations with ONE host sync
        hspr = (self._seg_syncs / self._seg_rounds) if self._seg_rounds \
            else (self._host_syncs / self._rounds if self._rounds
                  else 1.0)
        ca_hits, ca_misses = jax_cost.compile_ahead_counts()
        ca_hits0, ca_misses0 = self._ca0
        ca_errors, ca_first_error = jax_cost.compile_ahead_errors()
        return dict(
            rounds=self._rounds,
            host_syncs=self._host_syncs,
            host_syncs_per_round=hspr,
            device_rounds=self.device_rounds,
            device_rounds_source=self.device_rounds_source,
            pipeline=self.pipeline,
            compile_ahead=self.compile_ahead,
            compile_ahead_hits=ca_hits - ca_hits0,
            compile_ahead_misses=ca_misses - ca_misses0,
            compile_ahead_errors=ca_errors - self._ca_errors0,
            compile_ahead_first_error=(
                ca_first_error if ca_errors > self._ca_errors0 else None),
            host_blocked_s=jax_cost.host_blocked_s() - self._blocked0,
            devices=jax_cost._mesh_ndev(self.mesh),
            dispatches=jax_cost.dispatch_count() - self._dispatch0,
            signatures=sorted({s.signature for s in self._states}),
            natural_signatures=sorted({s.natural
                                       for s in self._states}),
            # per-signature mega-batch watermark trajectory, keyed
            # "d{ndims}_p{bucket}_{topology}"
            pad_watermarks={
                f"d{sig[0]}_p{sig[1]}_{sig[2]}": hist
                for sig, hist in self._wm_hist.items()},
            # watermark decays to a shape the fleet had run (warm) and
            # by the PAD_DECAY_RATIO test (cold)
            pad_decays=dict(self._pad_decays))

    def finish(self) -> Dict[str, SearchResult]:
        """Stop background compile-ahead work, freeze ``stats``, and
        return every task's result keyed by resolved name."""
        # compile-ahead jobs still queued were predicted for dispatches
        # that will never come — stop burning cores on them
        jax_cost.compile_ahead_quiesce()
        self.stats = self.stats_snapshot()
        return {st.name: self._result_for(st) for st in self._states}

    def run(self) -> Dict[str, SearchResult]:
        if not self._started:
            self._fixed_tasks = True
        self.start()
        while self.step():
            pass
        return self.finish()


def run_sweep(workloads: Sequence[Workload],
              platform: PlatformLike = "cloud",
              budget: int = 20_000, seed: int = 0,
              align_signatures: bool = True,
              device_rounds: Optional[int] = None, mesh=None,
              pipeline: bool = True, compile_ahead: bool = True,
              config: Optional[FleetConfig] = None,
              **es_kw) -> Dict[str, SearchResult]:
    """Convenience wrapper: one concurrent SparseMap search per workload
    (e.g. the paper's Table III list) on a shared platform.  An explicit
    ``config`` wins over the individual fleet kwargs (which predate
    :class:`FleetConfig` and remain for convenience)."""
    if config is None:
        config = FleetConfig(
            align_signatures=align_signatures, device_rounds=device_rounds,
            mesh=mesh, pipeline=pipeline, compile_ahead=compile_ahead)
    ms = MultiSearch(
        [SearchTask(wl, platform, budget=budget, seed=seed,
                    method_kw=dict(es_kw)) for wl in workloads],
        config)
    return ms.run()


def run_method_sweep(methods: Sequence[str],
                     workloads: Sequence[Workload],
                     platform: PlatformLike = "cloud",
                     budget: int = 20_000, seed: int = 0,
                     align_signatures: bool = True,
                     method_kw: Optional[Dict[str, Dict]] = None,
                     stats_out: Optional[Dict] = None,
                     device_rounds: Optional[int] = None, mesh=None,
                     device_execute: bool = True, pipeline: bool = True,
                     compile_ahead: bool = True,
                     config: Optional[FleetConfig] = None
                     ) -> Dict[str, Dict[str, SearchResult]]:
    """The full fig17-style grid — every method on every workload — as ONE
    concurrent :class:`MultiSearch` fleet, mega-batched per signature.
    Returns ``{method: {workload_name: SearchResult}}``; ``method_kw``
    maps method name -> factory kwargs; ``stats_out``, if given, receives
    the fleet's ``MultiSearch.stats``."""
    method_kw = method_kw or {}
    dup_m = [m for m, c in Counter(methods).items() if c > 1]
    dup_w = [n for n, c in Counter(w.name for w in workloads).items()
             if c > 1]
    if dup_m or dup_w:
        # the returned {method: {workload_name: ...}} grid would silently
        # drop one of the colliding searches — refuse instead
        raise ValueError(
            f"run_method_sweep needs unique methods and workload names; "
            f"duplicated methods={dup_m}, workload names={dup_w}")
    tasks = [SearchTask(wl, platform, budget=budget, seed=seed, method=m,
                        method_kw=dict(method_kw.get(m, {})))
             for m in methods for wl in workloads]
    if config is None:
        config = FleetConfig(
            align_signatures=align_signatures, device_rounds=device_rounds,
            mesh=mesh, device_execute=device_execute,
            pipeline=pipeline, compile_ahead=compile_ahead)
    ms = MultiSearch(tasks, config)
    flat = ms.run()
    grid: Dict[str, Dict[str, SearchResult]] = {m: {} for m in methods}
    i = 0
    for m in methods:
        for wl in workloads:
            grid[m][wl.name] = flat[ms.final_names[i]]
            i += 1
    if stats_out is not None:
        stats_out.update(ms.stats)
    return grid
