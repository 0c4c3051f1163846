"""Property tests: the JAX batch evaluator agrees with the numpy oracle —
on the default paper topology AND on the non-default registered ArchSpecs
— plus the pinned pre-refactor golden regression for ARCH_SPARSEMAP."""
import os
import zlib

import numpy as np
import pytest

try:        # hypothesis is an optional test extra (pyproject.toml)
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_shim import given, settings, strategies as st

from repro.configs.archs import (CLUSTER_CLOUD, DSTC_LIKE, EYERISS_LIKE,
                                 MAPLE_EDGE, QUANT_EDGE, SIGMA_LIKE,
                                 SYSTOLIC_MESH)
from repro.core import accel
from repro.core.cost_model import check_against_oracle, evaluate
from repro.core.encoding import GenomeSpec
from repro.core.jax_cost import JaxCostModel
from repro.core.workload import batched_spmm, spconv, spmm

CASES = [
    spmm("mm_small", 32, 64, 48, 0.2, 0.5),
    spmm("mm_dense", 124, 124, 124, 0.785, 0.785),
    spmm("mm_sparse", 128, 1024, 128, 0.006, 0.006),
    spconv("conv", 64, 32, 32, 256, 1, 1, 0.45, 0.252),
    batched_spmm("bmm", 4, 16, 32, 16, 0.3, 0.7),
]
PLATS = [accel.EDGE, accel.MOBILE, accel.CLOUD]


@pytest.mark.parametrize("wl", CASES, ids=[w.name for w in CASES])
@pytest.mark.parametrize("plat", PLATS, ids=[p.name for p in PLATS])
def test_agreement(wl, plat):
    spec = GenomeSpec(wl)
    jm = JaxCostModel(spec, plat)
    rng = np.random.default_rng(
        zlib.crc32(f"{wl.name}:{plat.name}".encode()))
    G = spec.random_genomes(rng, 400)
    out = jm(G)
    n_valid = 0
    for i, g in enumerate(G):
        rep = evaluate(spec.decode(g), plat)
        jv = bool(out["valid"][i])
        # skip razor-thin capacity margins (float32 vs float64)
        if rep.valid != jv:
            margin = min(
                abs(rep.glb_occupancy_bytes - plat.glb_bytes) /
                plat.glb_bytes if rep.valid else 1,
                abs(rep.pebuf_occupancy_bytes - plat.pe_buffer_bytes) /
                plat.pe_buffer_bytes if rep.valid else 1)
            assert margin < 5e-3, (
                f"genome {i}: oracle valid={rep.valid} ({rep.reason}) "
                f"jax valid={jv}")
            continue
        if rep.valid:
            n_valid += 1
            lg = np.log10(rep.edp)
            assert abs(lg - out["log10_edp"][i]) <= 2e-3 * max(abs(lg), 1), \
                f"genome {i}: edp oracle={rep.edp:.4e} jax log mismatch"
    # make sure the comparison is not vacuous for at least some cases
    if wl.name == "mm_small" and plat.name == "cloud":
        assert n_valid > 0


# ---------------------------------------------- non-default topologies


def _check_agreement(wl, arch, seed, n=64, require_valid=0):
    """Numpy-oracle vs JAX-kernel agreement on one (workload, arch),
    under the shared rule of ``cost_model.check_against_oracle``."""
    spec = GenomeSpec(wl, arch=arch)
    jm = JaxCostModel(spec, arch)
    rng = np.random.default_rng(seed)
    G = spec.random_genomes(rng, n)
    agr = check_against_oracle(spec, arch, G, jm(G))
    assert not agr.disagreements, agr.disagreements[:5]
    assert agr.both_valid >= require_valid
    return agr.both_valid


@st.composite
def small_workloads(draw):
    m = draw(st.integers(min_value=2, max_value=48))
    k = draw(st.integers(min_value=2, max_value=48))
    n = draw(st.integers(min_value=2, max_value=48))
    dp = draw(st.floats(min_value=0.01, max_value=1.0))
    dq = draw(st.floats(min_value=0.01, max_value=1.0))
    return spmm(f"mm_{m}x{k}x{n}", m, k, n, dp, dq)


@settings(max_examples=10, deadline=None)
@given(small_workloads(), st.integers(min_value=0, max_value=2**31 - 1))
def test_agreement_maple_edge(wl, seed):
    """2-store Maple-style arch (3 mapping levels, 2 S/G sites): the
    generic numpy model and the generic kernel must agree."""
    _check_agreement(wl, MAPLE_EDGE, seed)


@settings(max_examples=10, deadline=None)
@given(small_workloads(), st.integers(min_value=0, max_value=2**31 - 1))
def test_agreement_cluster_cloud(wl, seed):
    """4-store clustered arch (7 mapping levels, 4 S/G sites)."""
    _check_agreement(wl, CLUSTER_CLOUD, seed)


@settings(max_examples=10, deadline=None)
@given(small_workloads(), st.integers(min_value=0, max_value=2**31 - 1))
def test_agreement_systolic_mesh(wl, seed):
    """Mesh NoC (no read multicast, reduction-tree output collection):
    the NoC-aware fills accounting must agree numpy-vs-kernel."""
    _check_agreement(wl, SYSTOLIC_MESH, seed)


@settings(max_examples=10, deadline=None)
@given(small_workloads(), st.integers(min_value=0, max_value=2**31 - 1))
def test_agreement_quant_edge(wl, seed):
    """1-byte on-chip words: the traced per-edge width path of the
    kernel must agree with the width-parameterized numpy oracle."""
    _check_agreement(wl, QUANT_EDGE, seed)


@settings(max_examples=10, deadline=None)
@given(small_workloads(), st.integers(min_value=0, max_value=2**31 - 1))
def test_agreement_eyeriss_like(wl, seed):
    """Fractional NoC both ways (row multicast f=14, column reduction
    f=12 on the 12x14 mesh): the traced-discount kernel path must agree
    with the numpy oracle."""
    _check_agreement(wl, EYERISS_LIKE, seed)


@settings(max_examples=10, deadline=None)
@given(small_workloads(), st.integers(min_value=0, max_value=2**31 - 1))
def test_agreement_dstc_like(wl, seed):
    """Row multicast plus cluster-local reduction (both fractional) on a
    4-store hierarchy."""
    _check_agreement(wl, DSTC_LIKE, seed)


@settings(max_examples=10, deadline=None)
@given(small_workloads(), st.integers(min_value=0, max_value=2**31 - 1))
def test_agreement_sigma_like(wl, seed):
    """Full multicast with a fractional reduction tree over a 16384-wide
    spatial level."""
    _check_agreement(wl, SIGMA_LIKE, seed)


def test_new_archs_reach_valid_points():
    """The comparison on the new topologies must not be vacuous: the
    engineer-default design (balanced OS mapping, uncompressed formats,
    no S/G) is valid on both, and oracle == kernel on it."""
    from repro.core.baselines import fixed_mapping_genes_for_arch
    wl = spmm("mm_probe", 32, 64, 48, 0.2, 0.5)
    for arch in (MAPLE_EDGE, CLUSTER_CLOUD):
        spec = GenomeSpec(wl, arch=arch)
        g = np.zeros(spec.length, dtype=np.int64)
        for k, v in fixed_mapping_genes_for_arch(spec, arch).items():
            g[k] = v
        rep = evaluate(spec.decode(g), arch)
        assert rep.valid, f"{arch.name}: {rep.reason}"
        out = JaxCostModel(spec, arch)(g[None, :])
        assert bool(out["valid"][0]), arch.name
        lg = np.log10(rep.edp)
        assert abs(lg - out["log10_edp"][0]) <= 2e-3 * max(abs(lg), 1)


def test_genome_layout_scales_with_arch():
    wl = spmm("mm_layout", 32, 64, 48, 0.2, 0.5)
    base = GenomeSpec(wl)
    maple = GenomeSpec(wl, arch=MAPLE_EDGE)
    cluster = GenomeSpec(wl, arch=CLUSTER_CLOUD)
    assert len(base.segments["perm"]) == 5
    assert len(maple.segments["perm"]) == 3
    assert len(cluster.segments["perm"]) == 7
    assert len(base.segments["sg"]) == 3
    assert len(maple.segments["sg"]) == 2
    assert len(cluster.segments["sg"]) == 4
    assert int(base.gene_ub[base.segments["tiling"].start]) == 5
    assert int(maple.gene_ub[maple.segments["tiling"].start]) == 3
    assert int(cluster.gene_ub[cluster.segments["tiling"].start]) == 7


# ---------------------------------------------- pinned golden regression


GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "arch_sparsemap_golden.npz")
SEARCH_GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                             "search_golden.json")


def test_fixed_seed_searches_match_pre_refactor_goldens_bit_for_bit():
    """Fixed-seed end-to-end searches (engine RNG streams + kernel)
    reproduce the pre-refactor best-EDPs exactly (stored as float hex)."""
    import json

    from repro.configs.paper_workloads import by_name
    from repro.core import search
    gold = json.load(open(SEARCH_GOLDEN))
    r1 = search.run("sparsemap", by_name("mm1"), "cloud", budget=600,
                    seed=3)
    assert r1.best_edp.hex() == gold["sparsemap_mm1_cloud_b600_s3"]
    r2 = search.run("pso", by_name("mm3"), "cloud", budget=400, seed=1)
    assert r2.best_edp.hex() == gold["pso_mm3_cloud_b400_s1"]


def test_arch_sparsemap_matches_pre_refactor_goldens_bit_for_bit():
    """ARCH_SPARSEMAP (the default) must reproduce the pre-ArchSpec
    stack's numbers EXACTLY: the golden file holds seeded genome batches
    and their kernel outputs captured before the refactor."""
    g = np.load(GOLDEN)
    cases = [
        spmm("mm_small", 32, 64, 48, 0.2, 0.5),
        spmm("mm_sparse", 128, 1024, 128, 0.006, 0.006),
        spconv("conv", 64, 32, 32, 256, 1, 1, 0.45, 0.252),
        batched_spmm("bmm", 4, 16, 32, 16, 0.3, 0.7),
    ]
    for wl in cases:
        spec = GenomeSpec(wl)
        for plat in PLATS:
            key = f"{wl.name}:{plat.name}"
            G = g[f"{key}:genomes"]
            res = JaxCostModel(spec, plat)(G)
            np.testing.assert_array_equal(
                g[f"{key}:jax_valid"], np.asarray(res["valid"]),
                err_msg=f"{key}: valid drifted")
            for fld, out_key in (("jax_edp", "edp"),
                                 ("jax_energy", "energy_pj"),
                                 ("jax_cycles", "cycles")):
                np.testing.assert_array_equal(
                    g[f"{key}:{fld}"], np.asarray(res[out_key]),
                    err_msg=f"{key}: {out_key} not bit-identical")
            # numpy oracle (float64) on the captured prefix
            ov, oe = g[f"{key}:np_valid"], g[f"{key}:np_edp"]
            for i, row in enumerate(G[: len(ov)]):
                rep = evaluate(spec.decode(row), plat)
                assert rep.valid == ov[i], f"{key} row {i}"
                assert (rep.edp if rep.valid else np.inf) == oe[i], \
                    f"{key} row {i}: oracle EDP drifted"
