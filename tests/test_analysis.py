"""Contract linter + jaxpr auditor (repro.analysis).

Layer 1: every rule R1-R4 is proven to fire on a violating fixture and
stay silent on a conforming twin (tests/analysis_fixtures/); R5 is
exercised over the live registries and over deliberately broken fakes.
Layer 2: the jaxpr audit must pass on a live kernel family, detect a
deliberately-baked-constant kernel as a family-sharing failure, and
flag host callbacks.  The `python -m repro.analysis` gate itself must
exit 0 on the repo.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.analysis import lint_source, run_report
from repro.analysis.lint import Violation, suppressions
from repro.analysis.rules import ALL_RULES
from repro.analysis.rules.r1_traced_bake import TracedBakeRule
from repro.analysis.rules.r2_rng import RngDeterminismRule
from repro.analysis.rules.r3_deferred_sync import DeferredSyncRule
from repro.analysis.rules.r4_counter_lock import CounterLockRule
from repro.analysis.rules.r5_registry import (check_archs,
                                              check_density_families,
                                              check_registries,
                                              check_request_methods)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tests", "analysis_fixtures")


def _lint_fixture(name, rule_cls):
    path = os.path.join(FIXTURES, name)
    with open(path) as f:
        src = f.read()
    # force=True: fixtures live outside the rules' real target paths
    return lint_source(src, path, rules=[rule_cls()], force=True)


# ------------------------------------------------------------ rules R1-R4

@pytest.mark.parametrize("rule_cls,bad,ok,n_bad", [
    (TracedBakeRule, "r1_bad.py", "r1_ok.py", 4),
    (RngDeterminismRule, "r2_bad.py", "r2_ok.py", 4),
    (DeferredSyncRule, "r3_bad.py", "r3_ok.py", 3),
    (CounterLockRule, "r4_bad.py", "r4_ok.py", 3),
])
def test_rule_fires_on_bad_and_not_on_ok(rule_cls, bad, ok, n_bad):
    vs = _lint_fixture(bad, rule_cls)
    assert len(vs) == n_bad, [str(v) for v in vs]
    assert all(v.rule == rule_cls.rule_id for v in vs)
    assert _lint_fixture(ok, rule_cls) == []


def test_violation_render_and_sorting():
    vs = _lint_fixture("r4_bad.py", CounterLockRule)
    assert all(":" in str(v) and f"[{v.rule}]" in str(v) for v in vs)
    assert [v.line for v in vs] == sorted(v.line for v in vs)


def test_noqa_contract_suppression():
    path = os.path.join(FIXTURES, "noqa.py")
    with open(path) as f:
        src = f.read()
    sup = suppressions(src)
    assert any("R2" in rules for rules in sup.values())
    vs = lint_source(src, path, rules=[RngDeterminismRule()], force=True)
    # one of the two identical violations is suppressed, one remains
    assert len(vs) == 1
    assert "still_bad" in src.splitlines()[vs[0].line - 1] or \
        vs[0].line > min(sup)


def test_repo_is_lint_clean():
    rep = run_report(roots=[os.path.join(ROOT, "src"),
                            os.path.join(ROOT, "benchmarks"),
                            os.path.join(ROOT, "examples")],
                     include_jaxpr=False)
    assert rep["lint"]["violations"] == [], rep["lint"]["violations"]
    assert rep["ok"]


# ------------------------------------------------------------------- R5

def test_live_registries_conform():
    assert check_registries() == []


def test_r5_flags_bad_factory_and_orphan_segment_method():
    vs = check_request_methods(
        {"bad": (lambda spec: None), "notcallable": 3},
        segment_methods={"ghost", "bad"})
    msgs = "\n".join(str(v) for v in vs)
    assert "positional" in msgs
    assert "**kw" in msgs
    assert "not callable" in msgs
    assert "ghost" in msgs


def test_r5_flags_nonconforming_density_family():
    import dataclasses

    from repro.core.density import DensityModel

    @dataclasses.dataclass(frozen=True)
    class Mystery(DensityModel):
        family = "other_name"

    vs = check_density_families(
        {"mystery": (7, Mystery), "notamodel": (8, int)},
        jax_occ={}, base_cls=DensityModel)
    msgs = "\n".join(str(v) for v in vs)
    assert "does not match its registry key" in msgs
    assert "not overridden" in msgs
    assert "occupancy builder" in msgs
    assert "not a DensityModel subclass" in msgs


def test_r5_flags_param_vector_length_mismatch():
    from repro.core.arch import ARCH_SPARSEMAP

    class Truncated:
        topology = ARCH_SPARSEMAP.topology

        def param_vector(self):
            return ARCH_SPARSEMAP.param_vector()[:-1]

    vs = check_archs({"trunc": Truncated()})
    assert any("kernel layout" in v.message for v in vs)
    assert check_archs({"sparsemap": ARCH_SPARSEMAP}) == []


# ------------------------------------------------------- jaxpr audit

def _cloud_arch():
    from repro.core.arch import as_arch
    return as_arch("cloud")


def test_jaxpr_audit_one_family_clean_and_hashed():
    from repro.analysis.jaxpr_audit import audit_families
    findings, hashes = audit_families(archs={"cloud": _cloud_arch()},
                                      include_scan=False)
    assert findings == [], [str(v) for v in findings]
    assert set(hashes) == {"cloud/u/eval", "cloud/s/eval"}
    assert all(len(h) == 16 for h in hashes.values())


def test_baked_constant_kernel_fails_family_sharing():
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_audit import canonical_hash

    def make_baked(scale):
        const = jnp.full((4,), scale, jnp.float32)   # closure const

        def f(x):
            return x * const + float(scale)          # baked literal
        return f

    x = np.zeros(4, np.float32)
    h1 = canonical_hash(jax.make_jaxpr(make_baked(1.5))(x))
    h2 = canonical_hash(jax.make_jaxpr(make_baked(2.5))(x))
    assert h1 != h2      # the bug class the audit exists to catch

    def traced(x, s):     # the conforming twin: number rides as input
        return x * s

    g1 = jax.make_jaxpr(traced)(x, np.float32(1.5))
    g2 = jax.make_jaxpr(traced)(x, np.float32(2.5))
    assert canonical_hash(g1) == canonical_hash(g2)


def test_audit_flags_host_callback():
    import jax

    from repro.analysis.jaxpr_audit import audit_program

    def f(x):
        return jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    closed = jax.make_jaxpr(f)(np.ones(3, np.float32))
    vs = audit_program(closed, "fixture")
    assert any("callback" in v.message for v in vs)


def test_scan_alias_device_put_not_flagged():
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_audit import audit_program

    def f(xs):
        def body(c, x):
            # jnp.asarray on a traced value emits the alias-semantics
            # device_put the audit must NOT flag
            return c + jnp.asarray(x, jnp.float32), ()
        c, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
        return c

    closed = jax.make_jaxpr(f)(np.ones(4, np.float32))
    assert audit_program(closed, "fixture") == []


# ------------------------------------- compile-ahead key <-> struct audit

def _small_model():
    from repro.configs.paper_workloads import by_name
    from repro.core.encoding import GenomeSpec
    from repro.core.jax_cost import JaxCostModel
    arch = _cloud_arch()
    return JaxCostModel(GenomeSpec(by_name("mm1"), arch), arch)


def test_check_aot_jobs_accepts_real_builders():
    from repro.analysis.jaxpr_audit import check_aot_jobs
    from repro.core import jax_cost
    from repro.core.direct_encoding import DirectValueSpec
    m = _small_model()
    dspec = DirectValueSpec(m.spec)
    jobs = [
        jax_cost.stacked_compile_job(m, 64),
        jax_cost.scan_compile_job(m, B=8, k=2, n_parents=2, n_elite=1,
                                  genes_per=2, T=3),
        jax_cost.scan_compile_job(m, B=8, k=2, n_parents=2, n_elite=1,
                                  genes_per=2, T=1, restart=8),
        jax_cost.direct_scan_compile_job(
            m, B=8, k=2, n_parents=2, n_elite=1, genes_per=2, T=2,
            direct_len=dspec.length),
    ]
    vs = check_aot_jobs(jobs)
    assert vs == [], [str(v) for v in vs]


def test_check_aot_job_rejects_mismatched_key():
    from repro.analysis.jaxpr_audit import check_aot_job
    from repro.core import jax_cost
    m = _small_model()
    key, fn, structs = jax_cost.stacked_compile_job(m, 64)
    wrong = key[:5] + (128,)          # claims 128 rows, structs say 64
    assert check_aot_job(wrong, fn, structs)
    skey, sfn, sstructs = jax_cost.scan_compile_job(
        m, B=8, k=2, n_parents=2, n_elite=1, genes_per=2, T=1)
    wrong2 = skey[:5] + (2,) + skey[6:]   # claims T=2, structs say T=1
    assert check_aot_job(wrong2, sfn, sstructs)
    assert check_aot_job(key[:4] + ("mystery", 64), fn, structs)


# ------------------------------------------- steady-state shape predictor

def test_steady_rows_predictions():
    from repro.configs.paper_workloads import by_name
    from repro.core.baselines import steady_rows
    from repro.core.encoding import GenomeSpec
    spec = GenomeSpec(by_name("mm1"))
    # budget 300 -> pop 24, elite 2: init pop + per-generation children
    assert steady_rows("sparsemap", spec, 300, 0) == (24, 22)
    # random_mapper's single 300-row chunk exhausts the budget
    assert steady_rows("random_mapper", spec, 300, 0) == ()
    assert steady_rows("random_mapper", spec, 900, 0) == (388,)
    assert steady_rows("random_mapper", spec, 1600, 0) == (512,)
    assert steady_rows("pso", spec, 300, 0) == (50,)
    # the translatable subset is data-dependent -> unpredictable
    assert steady_rows("standard_es", spec, 300, 0) is None


def test_compile_ahead_jobs_include_steady_stacked_shape():
    """The fleet predictor must emit the decayed steady-state stacked
    shape (sum of survivors' per-round batches) and every predicted key
    must be consistent with its arg structs (the jaxpr-audit check)."""
    from repro.analysis.jaxpr_audit import check_aot_jobs
    from repro.configs.paper_workloads import by_name
    from repro.core import search

    wl = by_name("mm1")
    tasks = [
        search.SearchTask(wl, "cloud", budget=300, seed=0,
                          method="sparsemap"),
        search.SearchTask(wl, "cloud", budget=300, seed=0,
                          method="random_mapper"),
    ]
    ms = search.MultiSearch(tasks, compile_ahead=False)
    jobs = ms._compile_ahead_jobs(ms._task_infos())
    assert check_aot_jobs(jobs) == []
    stacked = [j[0] for j in jobs if j[0][4] == "stacked"]
    # round-1: calib rows (predicted); steady: one sparsemap task's
    # init-pop/children rows -> pad bucket 64
    assert any(k[5] == 64 for k in stacked), stacked


# ----------------------------------------------------------- module gate

@pytest.mark.slow
def test_module_gate_exits_zero():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--skip-jaxpr"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "lint 0 violation(s)" in proc.stderr


def test_all_rules_registered():
    ids = [r.rule_id for r in ALL_RULES]
    assert ids == ["R1", "R2", "R3", "R4"]
    assert Violation("R9", "x.py", 3, "m").rule == "R9"
