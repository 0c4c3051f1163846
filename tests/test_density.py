"""DensityModel hierarchy: brute-force enumeration oracles for every
built-in model's tile occupancy, the Uniform bit-for-bit golden
regression (explicit Uniform(d) == seed float semantics against
tests/golden/arch_sparsemap_golden.npz), numpy-vs-JAX agreement on
structured workloads, and the compilation-sharing / mega-batching
contract (a BlockNM family shares one XLA compilation; a mixed
uniform/banded/N:M fleet runs at 1.0 dispatches/round)."""
import itertools
import math
import os

import numpy as np
import pytest

try:        # hypothesis is an optional test extra (pyproject.toml)
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_shim import given, settings, strategies as st

from repro.core import jax_cost, search
from repro.core.cost_model import check_against_oracle, evaluate
from repro.core.density import (Banded, BlockNM, DensityModel, Uniform,
                                as_density, param_row, param_width)
from repro.core.encoding import GenomeSpec
from repro.core.jax_cost import JaxCostModel, eval_stacked
from repro.core.sparse import FMT_B, FMT_CP, FMT_RLE, TensorFormat, \
    fiber_tree_bytes
from repro.core.workload import TensorSpec, spmm


# ------------------------------------------- brute-force occupancy oracles


def _enum_uniform_nonempty(d: float, e: int) -> float:
    """P(a block of e i.i.d. Bernoulli(d) elements has >= 1 nonzero), by
    exhaustive enumeration of all 2^e patterns."""
    p = 0.0
    for bits in itertools.product((0, 1), repeat=e):
        k = sum(bits)
        if k > 0:
            p += (d ** k) * ((1.0 - d) ** (e - k))
    return p


def _enum_block_nm_nonempty(n: int, m: int, e: int) -> float:
    """P(a fixed window of e of an m-block's positions intersects the n
    uniformly placed nonzeros), enumerating all C(m, n) placements."""
    window = set(range(e))
    total = hits = 0
    for placement in itertools.combinations(range(m), n):
        total += 1
        if window & set(placement):
            hits += 1
    return hits / total


@settings(max_examples=30, deadline=None)
@given(st.floats(min_value=0.05, max_value=1.0),
       st.integers(min_value=1, max_value=7))
def test_uniform_occupancy_matches_enumeration(d, e):
    assert Uniform(d).block_nonempty(e) == \
        pytest.approx(_enum_uniform_nonempty(d, e), rel=1e-9, abs=1e-12)


@settings(max_examples=30, deadline=None)
@given(st.floats(min_value=0.05, max_value=1.0),
       st.floats(min_value=0.05, max_value=1.0),
       st.integers(min_value=1, max_value=7))
def test_banded_occupancy_matches_enumeration(frac, cov, e):
    # two-phase model: block in band w.p. cov (uniform at d/cov inside),
    # exactly empty outside
    d = frac * cov
    model = Banded(d, cov)
    expect = cov * _enum_uniform_nonempty(d / cov, e)
    assert model.block_nonempty(e) == \
        pytest.approx(expect, rel=1e-9, abs=1e-12)


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=1, max_value=8),
       st.integers(min_value=1, max_value=8),
       st.integers(min_value=1, max_value=10))
def test_block_nm_occupancy_matches_enumeration(n, m, e):
    n = min(n, m)
    model = BlockNM(n, m)
    if e <= m:
        expect = _enum_block_nm_nonempty(n, m, e)
        assert model.block_nonempty(e) == pytest.approx(expect, rel=1e-9)
    else:
        assert model.block_nonempty(e) == 1.0


@settings(max_examples=20, deadline=None)
@given(st.floats(min_value=0.02, max_value=0.98),
       st.integers(min_value=1, max_value=6),
       st.integers(min_value=2, max_value=8))
def test_model_invariants(d, n, m):
    """block_nonempty(1) == mean density; occupancy is monotone in the
    block size and bounded by [density, 1]."""
    n = min(n, m)
    models = [Uniform(d), Banded(d * 0.5, max(d, 0.5)), BlockNM(n, m)]
    for model in models:
        assert model.block_nonempty(1) == pytest.approx(model.density,
                                                        rel=1e-12)
        prev = 0.0
        for e in range(1, 2 * m + 2):
            occ = model.block_nonempty(e)
            assert prev - 1e-12 <= occ <= 1.0 + 1e-12
            prev = occ
        assert model.hit_rate() == pytest.approx(model.density)


def test_model_validation():
    with pytest.raises(ValueError):
        Uniform(0.0)
    with pytest.raises(ValueError):
        Uniform(1.5)
    with pytest.raises(ValueError):
        Banded(0.5, 0.25)        # in-band density would exceed 1
    with pytest.raises(ValueError):
        Banded(0.1, 1.5)
    with pytest.raises(ValueError):
        BlockNM(5, 4)
    with pytest.raises(ValueError):
        BlockNM(0, 4)
    assert BlockNM(2, 4).density == 0.5
    assert as_density(0.25) == Uniform(0.25)
    assert as_density(Banded(0.1, 0.5)) == Banded(0.1, 0.5)
    assert isinstance(as_density(1), Uniform)


def test_param_rows():
    """The traced rows carry [family code, hit rate, params...]."""
    w = param_width()
    for model, code, tail in [(Uniform(0.3), 0.0, (0.3,)),
                              (Banded(0.1, 0.5), 1.0, (0.1, 0.5)),
                              (BlockNM(2, 4), 2.0, (2.0, 4.0))]:
        row = param_row(model)
        assert len(row) == w
        assert row[0] == code
        assert row[1] == pytest.approx(model.hit_rate())
        assert row[2:2 + len(tail)] == tail


def test_unregistered_family_rejected():
    class Weird(DensityModel):
        family = "weird_unregistered"
    with pytest.raises(KeyError):
        param_row(Weird())


# --------------------------------------------- byte-model structure effects


def test_structure_moves_the_byte_model():
    """Same mean density, different structure, different bytes: a banded
    operand's big empty regions shrink keep-based metadata (RLE/CP),
    while a 2:4 operand's occupancy saturates faster than uniform."""
    fmt = TensorFormat("P", (FMT_RLE, FMT_CP), (64, 64))
    d = 0.125
    _, meta_u = fiber_tree_bytes(fmt, d)
    _, meta_b = fiber_tree_bytes(fmt, Banded(d, 0.25))
    assert meta_b < meta_u
    fmt2 = TensorFormat("Q", (FMT_B, FMT_CP), (8, 2))
    _, meta_u2 = fiber_tree_bytes(fmt2, 0.5)
    _, meta_nm = fiber_tree_bytes(fmt2, BlockNM(2, 4))
    assert meta_nm > meta_u2          # small blocks: N:M hits more often


def test_fiber_tree_bytes_float_equals_uniform_bitwise():
    rng = np.random.default_rng(7)
    for _ in range(50):
        lens = tuple(int(rng.integers(2, 32))
                     for _ in range(int(rng.integers(1, 4))))
        fmts = tuple(int(rng.integers(0, 4)) for _ in lens)
        fmt = TensorFormat("P", fmts, lens)
        d = float(rng.uniform(0.01, 1.0))
        assert fiber_tree_bytes(fmt, d) == fiber_tree_bytes(fmt, Uniform(d))


# ------------------------------------------------ golden: Uniform == seed


GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "arch_sparsemap_golden.npz")


def test_explicit_uniform_matches_seed_goldens_bit_for_bit():
    """Workloads declared with explicit Uniform(d) models reproduce the
    pre-DensityModel kernel outputs EXACTLY (same baked uniform kernel,
    same constants) against the pinned golden captures."""
    g = np.load(GOLDEN)
    wl = spmm("mm_small", 32, 64, 48, Uniform(0.2), Uniform(0.5))
    assert not wl.structured_density
    spec = GenomeSpec(wl)
    jm = JaxCostModel(spec, "cloud")
    assert jm.signature[3] == "u"
    key = "mm_small:cloud"
    G = g[f"{key}:genomes"]
    res = jm(G)
    np.testing.assert_array_equal(g[f"{key}:jax_valid"],
                                  np.asarray(res["valid"]))
    for fld, out_key in (("jax_edp", "edp"), ("jax_energy", "energy_pj"),
                        ("jax_cycles", "cycles")):
        np.testing.assert_array_equal(
            g[f"{key}:{fld}"], np.asarray(res[out_key]),
            err_msg=f"{out_key} drifted under explicit Uniform models")
    # numpy oracle on the captured prefix, bit-for-bit too
    ov, oe = g[f"{key}:np_valid"], g[f"{key}:np_edp"]
    for i, row in enumerate(G[: len(ov)]):
        rep = evaluate(spec.decode(row), "cloud")
        assert rep.valid == ov[i], f"row {i}"
        assert (rep.edp if rep.valid else np.inf) == oe[i], f"row {i}"


# ------------------------------------------- numpy-vs-JAX on structured


@st.composite
def structured_workloads(draw):
    m = draw(st.integers(min_value=2, max_value=40))
    k = draw(st.integers(min_value=2, max_value=40))
    n = draw(st.integers(min_value=2, max_value=40))
    kind = draw(st.integers(min_value=0, max_value=2))
    if kind == 0:
        cov = draw(st.floats(min_value=0.1, max_value=1.0))
        frac = draw(st.floats(min_value=0.05, max_value=1.0))
        dp = Banded(frac * cov, cov)
    elif kind == 1:
        mm = draw(st.integers(min_value=2, max_value=8))
        nn = draw(st.integers(min_value=1, max_value=8))
        dp = BlockNM(min(nn, mm), mm)
    else:
        dp = draw(st.floats(min_value=0.05, max_value=1.0))
    qm = draw(st.integers(min_value=2, max_value=8))
    qn = draw(st.integers(min_value=1, max_value=8))
    dq = BlockNM(min(qn, qm), qm)
    return spmm(f"smm_{m}x{k}x{n}", m, k, n, dp, dq)


@settings(max_examples=10, deadline=None)
@given(structured_workloads(),
       st.integers(min_value=0, max_value=2**31 - 1))
def test_structured_agreement_numpy_vs_jax(wl, seed):
    """The structured kernel variant (traced family codes/params) must
    agree with the DensityModel-aware numpy oracle."""
    spec = GenomeSpec(wl)
    jm = JaxCostModel(spec, "cloud")
    assert jm.signature[3].startswith("s:")
    rng = np.random.default_rng(seed)
    G = spec.random_genomes(rng, 64)
    agr = check_against_oracle(spec, "cloud", G, jm(G))
    assert not agr.disagreements, agr.disagreements[:5]


# ---------------------------------------- compilation sharing / promotion


def test_block_nm_family_shares_one_compilation():
    """An N:M sweep (1:4, 2:4, 3:4, 2:8 ...) is ONE signature — n and m
    are traced numbers, not structural."""
    search.clear_cache()
    wls = [spmm(f"fam_{n}_{m}", 24, 36, 20, 0.4, BlockNM(n, m))
           for n, m in ((1, 4), (2, 4), (3, 4), (2, 8))]
    models = [JaxCostModel(GenomeSpec(w), "cloud") for w in wls]
    assert len({m.signature for m in models}) == 1
    rng = np.random.default_rng(0)
    batches = [GenomeSpec(w).random_genomes(rng, 32) for w in wls]
    for m, b in zip(models, batches):
        m(b)
    compiles = jax_cost.compilation_count()
    assert compiles == 1, f"family split compilations: {compiles}"
    # the mega-batch path shares too (one more compile for the stacked
    # kernel variant, then flat across the family)
    eval_stacked(models, batches)
    eval_stacked(list(reversed(models)), list(reversed(batches)))
    assert jax_cost.compilation_count() == compiles + 1


def test_uniform_promotion_agrees_with_baked_kernel():
    """A uniform workload promoted onto the structured kernel (so it can
    mega-batch with structured peers) evaluates to the same designs'
    costs as the baked uniform kernel."""
    wl = spmm("promo", 32, 64, 48, 0.2, 0.5)
    spec = GenomeSpec(wl)
    base = JaxCostModel(spec, "cloud")
    promo = JaxCostModel(spec, "cloud", structured=True)
    assert base.signature != promo.signature
    assert promo.signature[3].startswith("s:")
    G = spec.random_genomes(np.random.default_rng(3), 128)
    a, b = base(G), promo(G)
    np.testing.assert_array_equal(a["valid"], b["valid"])
    for k in ("cycles", "energy_pj"):
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=1e-6)


def test_structured_workload_refuses_uniform_kernel():
    wl = spmm("refuse", 8, 8, 8, 0.5, BlockNM(2, 4))
    with pytest.raises(ValueError):
        JaxCostModel(GenomeSpec(wl), "cloud", structured=False)


def test_mixed_density_fleet_one_dispatch_per_round():
    """run_method_sweep over a mixed uniform/banded/N:M fleet:
    density-mode alignment promotes the group onto one structured
    signature — one mega-batch dispatch per round."""
    search.clear_cache()
    wls = [spmm("mix_u", 16, 24, 16, 0.5, 0.5),
           spmm("mix_b", 24, 16, 16, Banded(0.1, 0.25), 0.9),
           spmm("mix_nm", 16, 16, 24, 0.8, BlockNM(2, 4))]
    stats = {}
    grid = search.run_method_sweep(["sparsemap", "random_mapper"], wls,
                                   "cloud", budget=200, seed=0,
                                   stack_batches=True, stats_out=stats)
    assert len(stats["signatures"]) == 1
    assert stats["signatures"][0][3].startswith("s:")
    assert stats["dispatches"] == stats["rounds"]
    for m in grid:
        for w in grid[m]:
            assert grid[m][w].evals >= 200


def test_cache_key_distinguishes_density_models():
    """Two same-shape workloads differing only in density structure must
    not share an evaluator (same aliasing class as the PR 2 bug)."""
    a = spmm("twin_d", 16, 16, 16, 0.5, 0.5)
    b = spmm("twin_d", 16, 16, 16, 0.5, BlockNM(2, 4))
    sa, ea = search.get_evaluator(a, "cloud")
    sb, eb = search.get_evaluator(b, "cloud")
    assert ea is not eb
    assert a.cache_key() != b.cache_key()


def test_tensor_spec_density_views():
    t = TensorSpec("P", ("M", "K"), 0.25)
    assert t.density_model == Uniform(0.25)
    assert t.mean_density == 0.25
    t2 = TensorSpec("Q", ("K", "N"), BlockNM(2, 4))
    assert t2.mean_density == 0.5
    wl = spmm("views", 8, 8, 8, Banded(0.1, 0.5), 0.5)
    assert wl.density_of("P") == pytest.approx(0.1)
    assert wl.density_model_of("P") == Banded(0.1, 0.5)
    assert wl.density_model_of("Z").family == "uniform"
    assert wl.density_of("Z") == pytest.approx(wl.output_density())
    assert wl.structured_density


def test_block_nm_float_windows_interpolate():
    """The log-gamma form handles fractional window sizes (the kernel's
    tile extents are float products) and stays within the integer
    endpoints."""
    model = BlockNM(2, 6)
    lo, hi = model.block_nonempty(2), model.block_nonempty(3)
    mid = model.block_nonempty(2.5)
    assert lo < mid < hi
    assert math.isclose(model.block_nonempty(4.0),
                        1.0 - 1.0 / 15.0, rel_tol=1e-9)
