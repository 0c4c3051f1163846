"""Layer 2 — jaxpr audit: trace every registered kernel family and
statically assert the contracts the AST linter cannot see.

For each registered topology (uniform and structured-density kernel
variants, fractional-NoC schemes included — they ride in the arch zoo's
registered specs) this module traces the un-jitted vmapped row kernel
and the device-resident ES scan programs with :func:`jax.make_jaxpr`
and walks the closed jaxpr recursively:

* **no host callbacks** anywhere (``pure_callback`` / ``io_callback`` /
  ``debug_callback`` / infeed/outfeed) — a callback inside a kernel
  re-inserts the host sync the pipelined dispatch path removed;
* **no float64** — no ``convert_element_type`` to f64 and no f64
  equation outputs (the contract dtype is float32 end-to-end);
* **no transfer ops inside ``lax.scan`` bodies** (``device_put`` in a
  scan body forces a per-generation transfer);
* **one compilation per family** — the same program traced from two
  same-structure / different-numbers specs (every numeric field of the
  arch perturbed) must produce byte-identical canonicalized jaxprs.  A
  number baked into the program surfaces as a differing literal/const
  and fails the diff.

Findings are reported as :class:`repro.analysis.lint.Violation` rows
with rule id ``JAXPR`` so both layers share one report format.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from .lint import Violation

RULE_ID = "JAXPR"

#: host-callback / transfer primitives forbidden anywhere in a kernel
DENY_GLOBAL = frozenset({
    "pure_callback", "io_callback", "callback", "debug_callback",
    "host_callback_call", "infeed", "outfeed",
})
#: additionally forbidden inside lax.scan/while bodies
DENY_SCAN = DENY_GLOBAL | {"device_put"}

#: primitives whose sub-jaxprs execute inside the device loop
_LOOP_PRIMS = frozenset({"scan", "while"})

#: batch size used for tracing (any power of two works; shapes only)
_TRACE_B = 8


# ------------------------------------------------------------ jaxpr walk

def _sub_jaxprs(val) -> Iterator:
    """Yield every Jaxpr/ClosedJaxpr nested in an eqn params value."""
    import jax.core as jcore
    Closed = getattr(jcore, "ClosedJaxpr", None)
    Jaxpr = getattr(jcore, "Jaxpr", None)
    if Closed is not None and isinstance(val, Closed):
        yield val.jaxpr
    elif Jaxpr is not None and isinstance(val, Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _sub_jaxprs(v)


def iter_eqns(jaxpr, in_scan: bool = False) -> Iterator[Tuple[object, bool]]:
    """Depth-first (eqn, inside-device-loop) pairs over a jaxpr and all
    nested jaxprs (pjit bodies, vmap/scan/cond sub-programs)."""
    for eqn in jaxpr.eqns:
        yield eqn, in_scan
        child_scan = in_scan or eqn.primitive.name in _LOOP_PRIMS
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub, child_scan)


def _is_f64(dtype) -> bool:
    try:
        return np.dtype(dtype) == np.float64
    except TypeError:
        return False


def _is_real_transfer(eqn) -> bool:
    """``device_put`` with every target device/src ``None`` is the
    alias-semantics no-op ``jnp.asarray`` emits on traced values — XLA
    elides it.  Only placements naming an actual device/committed src
    move bytes."""
    devs = eqn.params.get("devices", ())
    srcs = eqn.params.get("srcs", ())
    return any(d is not None for d in devs) or \
        any(s is not None for s in srcs)


def audit_program(closed, family: str) -> List[Violation]:
    """Walk one ClosedJaxpr and report every contract breach."""
    out: List[Violation] = []
    where = f"jaxpr:{family}"
    for eqn, in_scan in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in DENY_GLOBAL:
            out.append(Violation(
                RULE_ID, where, 0,
                f"host callback primitive `{name}` in kernel program — "
                f"re-inserts a host sync into the device path"))
        elif in_scan and name in DENY_SCAN and \
                (name != "device_put" or _is_real_transfer(eqn)):
            out.append(Violation(
                RULE_ID, where, 0,
                f"transfer primitive `{name}` inside a lax.scan body — "
                f"forces a per-generation device<->host transfer"))
        if name == "convert_element_type" and \
                _is_f64(eqn.params.get("new_dtype")):
            out.append(Violation(
                RULE_ID, where, 0,
                "convert_element_type to float64 in kernel program — "
                "the contract dtype is float32 end-to-end"))
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and _is_f64(getattr(aval, "dtype", None)):
                out.append(Violation(
                    RULE_ID, where, 0,
                    f"float64 output of `{name}` in kernel program"))
                break
    return out


def canonical_hash(closed) -> str:
    """Canonicalized program hash: the printed jaxpr (variable names are
    assigned deterministically by trace order) plus shape/dtype/VALUE of
    every closure constant.  Baked numbers live exactly there — as
    literals in the printed program or as consts — so same-structure /
    different-numbers traces collide iff nothing was baked."""
    h = hashlib.sha1()
    h.update(str(closed.jaxpr).encode())
    for c in closed.consts:
        a = np.asarray(c)
        h.update(repr((a.shape, str(a.dtype))).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# ------------------------------------------------- family trace builders

def _base_workload():
    from repro.configs.paper_workloads import mm_workloads
    for w in mm_workloads():
        if not w.structured_density:
            return w
    raise RuntimeError("no uniform-density paper workload found")


def _model_for(arch, structured: bool):
    from repro.core.encoding import GenomeSpec
    from repro.core.jax_cost import JaxCostModel
    spec = GenomeSpec(_base_workload(), arch)
    return JaxCostModel(spec, arch, structured=True if structured
                        else None)


def _perturb(spec):
    """Same structure, different numbers: every numeric field of the
    arch scaled by a field-specific factor.  Structure (level count,
    spatial-ness, NoC schemes, energy-group layout, word-bytes
    uniformity) is preserved, so the topology fingerprint — and with it
    the compilation signature — must not change."""
    from repro.core.arch import ArchSpec

    levels = []
    for lv in spec.levels:
        noc = dataclasses.replace(
            lv.noc,
            multicast_fanout=(None if lv.noc.multicast_fanout is None
                              else lv.noc.multicast_fanout * 2),
            reduction_fanout=(None if lv.noc.reduction_fanout is None
                              else lv.noc.reduction_fanout * 2))
        levels.append(dataclasses.replace(
            lv,
            capacity_bytes=(None if lv.capacity_bytes is None
                            else lv.capacity_bytes * 2),
            fill_energy=tuple(
                (nm, tuple(e * 1.3 for e in comps))
                for nm, comps in lv.fill_energy),
            fill_bandwidth_bytes_per_cycle=(
                None if lv.fill_bandwidth_bytes_per_cycle is None
                else lv.fill_bandwidth_bytes_per_cycle * 1.5),
            word_bytes=(None if lv.word_bytes is None
                        else lv.word_bytes * 0.5),
            # fanout VALUE is traced; spatial-ness (>1) is structural
            fanout=lv.fanout * 2 if lv.fanout > 1 else lv.fanout,
            noc=noc))
    return ArchSpec(spec.name + "+perturbed", tuple(levels),
                    e_mac=spec.e_mac * 1.7, clock_hz=spec.clock_hz)


def _trace_eval(model):
    """ClosedJaxpr of the un-jitted row kernel (:func:`_build_eval_one`,
    the closure every dispatch path vmaps), vmapped over rows with the
    workload constants broadcast."""
    import jax

    from repro.core.jax_cost import _build_eval_one, _row_structs
    eval_one = _build_eval_one(model.d, model.n_pad, model.arch.topology,
                               model.dens_key)
    veval = jax.vmap(eval_one, in_axes=(0, 0, 0, 0) + (None,) * 9)
    rows = tuple(np.zeros(s.shape, s.dtype)
                 for s in _row_structs(model, _TRACE_B))
    return jax.make_jaxpr(veval)(*rows, *model._np_consts)


def _zeros_like_structs(tree):
    import jax
    return jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, s.dtype), tree)


def _trace_scan(model, restart: int = 0):
    """ClosedJaxpr of the device-resident ES scan program (the
    ``run_segments`` fold), un-jitted, one task, tiny shapes."""
    import jax

    from repro.core.jax_cost import _scan_program, scan_compile_job
    _, _, structs = scan_compile_job(model, B=_TRACE_B, k=2, n_parents=2,
                                     n_elite=1, genes_per=2, T=1,
                                     restart=restart)
    fn = _scan_program(model.d, model.n_pad, model.arch.topology,
                       model.dens_key, 2, 1, 2, restart,
                       model._const_layout)
    return jax.make_jaxpr(fn)(*_zeros_like_structs(structs))


def _trace_direct_scan(model):
    """ClosedJaxpr of the ``standard_es`` direct-coordinate scan."""
    import jax

    from repro.core.direct_encoding import DirectValueSpec
    from repro.core.jax_cost import (_direct_scan_program,
                                     direct_scan_compile_job)
    _, _, structs = direct_scan_compile_job(
        model, B=_TRACE_B, k=2, n_parents=2, n_elite=1, genes_per=2,
        T=1, direct_len=DirectValueSpec(model.spec).length)
    fn = _direct_scan_program(model.d, model.n_pad, model.arch.topology,
                              model.dens_key, 2, 1, 2, model._const_layout)
    return jax.make_jaxpr(fn)(*_zeros_like_structs(structs))


# --------------------------------------------------------- family sweep

def _registered_archs() -> Dict[str, object]:
    from repro.core.arch import ARCH_SPARSEMAP, registered_archs
    archs = dict(registered_archs())
    archs.setdefault("sparsemap", ARCH_SPARSEMAP)
    # same-topology aliases (edge/mobile/cloud platforms, sparsemap vs
    # cloud) trace identical programs; audit one name per fingerprint
    seen = {}
    for name in sorted(archs):
        fp = archs[name].topology.fingerprint
        if fp not in seen:
            seen[fp] = name
    return {name: archs[name] for name in sorted(seen.values())}


def _family_pair(arch, structured: bool, tracer) -> Tuple[str, str, str]:
    """(hash_base, hash_perturbed, signature check message or '')."""
    base = _model_for(arch, structured)
    pert = _model_for(_perturb(arch), structured)
    msg = ""
    if base.signature != pert.signature:
        msg = (f"numeric perturbation changed the compilation signature "
               f"{base.signature} -> {pert.signature} — a number leaked "
               f"into the structural key")
    return canonical_hash(tracer(base)), canonical_hash(tracer(pert)), msg


def audit_families(archs: Optional[Dict[str, object]] = None,
                   include_scan: bool = True,
                   ) -> Tuple[List[Violation], Dict[str, str]]:
    """Trace every registered kernel family; return (findings, hashes).

    ``hashes`` maps family label -> canonical jaxpr hash of the base
    trace (recorded into ``BENCH_sweep.json`` so hash drift across PRs
    is visible in review).
    """
    if archs is None:
        archs = _registered_archs()
    findings: List[Violation] = []
    hashes: Dict[str, str] = {}

    def run(label: str, arch, structured: bool, tracer) -> None:
        base = _model_for(arch, structured)
        closed = tracer(base)
        findings.extend(audit_program(closed, label))
        h_base = canonical_hash(closed)
        hashes[label] = h_base
        pert = _model_for(_perturb(arch), structured)
        if base.signature != pert.signature:
            findings.append(Violation(
                RULE_ID, f"jaxpr:{label}", 0,
                f"numeric perturbation changed the compilation "
                f"signature {base.signature} -> {pert.signature} — a "
                f"number leaked into the structural key"))
            return
        h_pert = canonical_hash(tracer(pert))
        if h_base != h_pert:
            findings.append(Violation(
                RULE_ID, f"jaxpr:{label}", 0,
                f"family sharing violated: same-structure / "
                f"different-numbers traces hash {h_base} vs {h_pert} — "
                f"a spec number is baked into the XLA program instead "
                f"of riding in the traced param vector"))

    for name, arch in archs.items():
        run(f"{name}/u/eval", arch, False, _trace_eval)
        run(f"{name}/s/eval", arch, True, _trace_eval)
        if include_scan:
            run(f"{name}/u/scan", arch, False, _trace_scan)
    if include_scan and archs:
        # deeper scan variants on one representative topology: the
        # structured fold, the stagnation-restart carry, and the
        # standard_es direct-coordinate translate-in-scan program
        name = ("cloud" if "cloud" in archs else sorted(archs)[0])
        arch = archs[name]
        run(f"{name}/s/scan", arch, True, _trace_scan)
        run(f"{name}/u/scan_r8", arch, False,
            lambda m: _trace_scan(m, restart=8))
        run(f"{name}/u/dscan", arch, False, _trace_direct_scan)
    return findings, hashes


def family_hashes(include_scan: bool = False) -> Dict[str, str]:
    """Just the canonical hashes (benchmark provenance section)."""
    _, hashes = audit_families(include_scan=include_scan)
    return hashes


# ------------------------------------------- compile-ahead key validation

def check_aot_job(key: Tuple, fn, arg_structs) -> List[Violation]:
    """Validate one ``compile_ahead`` job triple: the AOT registry key
    must be consistent with the argument structs it will be compiled
    for, per dispatch-path tag — a mismatched key can never be *found*
    at dispatch (the lookup misses), so every prediction with a bad key
    is a silently wasted compile."""
    import jax

    from repro.core.jax_cost import (SCAN_TAG_RE, _fields_width,
                                     _scan_in_fields)
    out: List[Violation] = []
    where = "aot:" + "/".join(str(k) for k in key[:5])

    def bad(msg: str) -> None:
        out.append(Violation(RULE_ID, where, 0, msg))

    if len(key) < 6:
        bad(f"AOT key {key!r} too short — expected sig + tag + shape")
        return out
    d, n_pad, fp, dens_key, tag = key[0], key[1], key[2], key[3], key[4]
    if not (isinstance(d, int) and isinstance(n_pad, int)
            and isinstance(fp, str) and len(fp) == 8
            and isinstance(dens_key, str)):
        bad(f"AOT key {key!r} does not start with a "
            f"(ndims, n_pad, fingerprint, dens_key) signature")
        return out
    leaves = jax.tree_util.tree_leaves(arg_structs)
    if not callable(fn):
        bad("job fn is not callable")

    if tag == "stacked":
        if len(key) != 7 or len(leaves) != 2:
            bad(f"stacked job {key!r} must be sig + (tag, rows, slots) "
                f"over (row buffer, table), got {len(leaves)} arg leaves")
            return out
        rows, table = leaves
        if rows.shape[0] != key[5] or rows.dtype != np.int32:
            bad(f"stacked row buffer {rows.shape} {rows.dtype} is not "
                f"({key[5]}, W) int32 as the key says")
        if table.shape[0] != key[6] or table.dtype != np.float32:
            bad(f"stacked table {table.shape} {table.dtype} is not "
                f"({key[6]}, width) float32 as the key says")
    elif isinstance(tag, str) and SCAN_TAG_RE.match(tag):
        if len(key) != 10 or len(arg_structs) != 3:
            bad(f"scan-family key {key!r} must be sig + (tag, T, B, k, "
                f"n_children, table slots) over (carries, rows, table)")
            return out
        T, B, k, n_children, slots = key[5:10]
        carries, rows, table = arg_structs
        if len(carries) != T or carries[0].shape[0] != B:
            bad(f"{tag} carry structs {len(carries)} x "
                f"{carries[0].shape} != (T={T}) x (B={B}, ...) in the key")
            return out
        fam, _, _, genes_per, restart = SCAN_TAG_RE.match(tag).groups()
        kind = "direct" if fam == "dscan" else "es"
        width = _fields_width(_scan_in_fields(
            kind, k, n_children, int(genes_per), carries[0].shape[1] - 1,
            int(restart or 0), d))
        if rows.shape != (T, width) or rows.dtype != np.int32:
            bad(f"{tag} packed rows {rows.shape} {rows.dtype} are not "
                f"(T={T}, {width}) int32 for k={k}, "
                f"n_children={n_children} in the key")
        if table.shape[0] != slots or table.dtype != np.float32:
            bad(f"{tag} table {table.shape} {table.dtype} is not "
                f"({slots}, width) float32 as the key says")
    else:
        bad(f"unknown AOT tag {tag!r}")
    return out


def check_aot_jobs(jobs) -> List[Violation]:
    out: List[Violation] = []
    for key, fn, structs in jobs:
        out.extend(check_aot_job(key, fn, structs))
    return out
