"""Per-level word widths + NoC multicast/reduction (the ArchSpec axis
added on top of PR 3): numpy fills/cost semantics, topology fingerprints
and compilation sharing, the pinned CostReport goldens for the
non-default archs, and the end-to-end acceptance sweeps on the
systolic-mesh and quantized-edge topologies."""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.configs.archs import (CLUSTER_CLOUD, DSTC_LIKE, EYERISS_LIKE,
                                 MAPLE_EDGE, QUANT_EDGE, SIGMA_LIKE,
                                 SYSTOLIC_MESH)
from repro.core import jax_cost, search
from repro.core.arch import (ARCH_SPARSEMAP, ArchSpec, NoCSpec,
                             StorageLevel)
from repro.core.baselines import (fixed_mapping_genes_for_arch,
                                  manual_sparse_genes)
from repro.core.cost_model import evaluate
from repro.core.encoding import GenomeSpec
from repro.core.jax_cost import JaxCostModel
from repro.core.mapping import Mapping, balanced_mapping_for_arch
from repro.core.sparse import SG_GATE_BOTH
from repro.core.workload import spmm


def _three_store(noc: NoCSpec, name: str) -> ArchSpec:
    return ArchSpec(name, (
        StorageLevel("dram"),
        StorageLevel("glb", capacity_bytes=256 * 1024,
                     fill_energy=(("dram", (100.0,)),), sg_site="L2"),
        StorageLevel("reg", fill_energy=(("glb", (3.0,)),),
                     fanout=4, noc=noc),
    ))


def _mapping(arch: ArchSpec, wl, spatial_dim: str) -> Mapping:
    """All three dims are 4; ``spatial_dim`` unrolls on L2_S, the other
    two stay on L1_T."""
    rest = [d for d in wl.dim_order if d != spatial_dim]
    factors = ({d: 4 for d in rest}, {}, {spatial_dim: 4})
    perms = tuple(tuple(wl.dim_order) for _ in range(3))
    return Mapping(workload=wl, factors=factors, perms=perms, arch=arch)


# ------------------------------------------------------------- NoC fills


def test_unicast_noc_multiplies_irrelevant_spatial_read_traffic():
    """An irrelevant spatial loop is free under multicast (one copy
    serves all instances) and costs one copy per instance without it —
    wherever it sits in the nest, including the reuse suffix."""
    wl = spmm("noc_wl", 4, 4, 4, 0.5, 0.5)
    mcast = _three_store(NoCSpec(), "noc_mcast")
    ucast = _three_store(NoCSpec(multicast=False), "noc_ucast")
    # N unrolled spatially: irrelevant to P[M,K], relevant to Q and Z
    m_m = _mapping(mcast, wl, "N")
    m_u = _mapping(ucast, wl, "N")
    assert m_u.fills("reg", "P") == 4 * m_m.fills("reg", "P")
    assert m_u.fills("reg", "Q") == m_m.fills("reg", "Q")
    assert m_u.fills("reg", "Z") == m_m.fills("reg", "Z")


def test_no_reduction_noc_multiplies_partial_output_traffic():
    """Spatially-unrolled contraction (K on L2_S): with in-network
    reduction one reduced result crosses the edge; without it every
    instance's partials cross.  Reads are untouched by the reduction
    flag."""
    wl = spmm("noc_wl", 4, 4, 4, 0.5, 0.5)
    tree = _three_store(NoCSpec(), "noc_tree")
    flat = _three_store(NoCSpec(reduction=False), "noc_flat")
    m_t = _mapping(tree, wl, "K")
    m_f = _mapping(flat, wl, "K")
    assert m_f.fills("reg", "Z") == 4 * m_t.fills("reg", "Z")
    assert m_f.fills("reg", "P") == m_t.fills("reg", "P")
    assert m_f.fills("reg", "Q") == m_t.fills("reg", "Q")


def test_fractional_noc_interpolates_between_all_and_none():
    """A fractional scheme discounts irrelevant-spatial read traffic by
    ``max(S / fanout, 1)``: fanout 1 reproduces unicast, fanout >= S
    reproduces full multicast, and in between the edge carries S/fanout
    copies.  Same story for cluster-local reduction on the output."""
    wl = spmm("frac_wl", 4, 4, 4, 0.5, 0.5)
    mcast = _three_store(NoCSpec(), "frac_mc_all")
    ucast = _three_store(NoCSpec(multicast=False), "frac_mc_none")
    half = _three_store(NoCSpec(multicast="row", multicast_fanout=2.0),
                        "frac_mc_2")
    wide = _three_store(NoCSpec(multicast="row", multicast_fanout=8.0),
                        "frac_mc_8")
    base = _mapping(mcast, wl, "N").fills("reg", "P")
    assert _mapping(ucast, wl, "N").fills("reg", "P") == 4 * base
    assert _mapping(half, wl, "N").fills("reg", "P") == 2 * base
    assert _mapping(wide, wl, "N").fills("reg", "P") == base
    # relevant-tensor fills never see the discount
    assert _mapping(half, wl, "N").fills("reg", "Q") == \
        _mapping(mcast, wl, "N").fills("reg", "Q")
    tree = _three_store(NoCSpec(), "frac_red_all")
    cluster = _three_store(
        NoCSpec(reduction="cluster", reduction_fanout=2.0), "frac_red_2")
    assert _mapping(cluster, wl, "K").fills("reg", "Z") == \
        2 * _mapping(tree, wl, "K").fills("reg", "Z")


def test_fractional_noc_validation():
    """Fractional schemes need a positive fanout; all/none take none."""
    with pytest.raises(ValueError):
        NoCSpec(multicast="row")
    with pytest.raises(ValueError):
        NoCSpec(multicast="row", multicast_fanout=0.0)
    with pytest.raises(ValueError):
        NoCSpec(multicast=True, multicast_fanout=4.0)
    with pytest.raises(ValueError):
        NoCSpec(reduction="")


def test_fractional_noc_family_shares_one_compilation():
    """The scheme is structural, the discount fanout is traced: two
    same-scheme archs with different fanouts share the topology, the
    signature AND the compiled kernel object; labels don't split either.
    The discount rides in the param-vector tail."""
    a = _three_store(NoCSpec(multicast="row", multicast_fanout=2.0),
                     "frac_fam_a")
    b = _three_store(NoCSpec(multicast="bus", multicast_fanout=7.0),
                     "frac_fam_b")
    c = _three_store(NoCSpec(), "frac_fam_c")
    assert a.topology == b.topology
    assert a.topology != c.topology
    assert a.param_vector()[-1] == 2.0
    assert b.param_vector()[-1] == 7.0
    wl = spmm("frac_sig", 16, 16, 16, 0.5, 0.5)
    m_a = JaxCostModel(GenomeSpec(wl, arch=a), a)
    m_b = JaxCostModel(GenomeSpec(wl, arch=b), b)
    assert m_a.signature == m_b.signature
    job_a, job_b = (jax_cost.stacked_compile_job(m, 64) for m in (m_a, m_b))
    assert job_a[0] == job_b[0] and job_a[1] is job_b[1]
    assert m_a.signature != JaxCostModel(GenomeSpec(wl, arch=c), c).signature


def test_default_noc_is_bitwise_neutral():
    """An explicitly-default NoCSpec leaves the topology and all numbers
    of the paper arch untouched."""
    spec = ArchSpec("explicit_noc", tuple(
        lv if k == 0 else dataclasses.replace(lv, noc=NoCSpec(True, True))
        for k, lv in enumerate(ARCH_SPARSEMAP.levels)))
    assert spec.topology == ARCH_SPARSEMAP.topology
    np.testing.assert_array_equal(spec.param_vector(),
                                  ARCH_SPARSEMAP.param_vector())


# ---------------------------------------------------------- word widths


def _quant_twin(word_bytes):
    lv = [dataclasses.replace(l, word_bytes=word_bytes) if k > 0 else l
          for k, l in enumerate(ARCH_SPARSEMAP.levels)]
    return ArchSpec(f"wb{word_bytes:g}", tuple(lv),
                    e_mac=ARCH_SPARSEMAP.e_mac,
                    clock_hz=ARCH_SPARSEMAP.clock_hz)


def test_halving_word_width_halves_uncompressed_bytes():
    """With uncompressed formats every byte count is linear in the word
    width: occupancies, traffic, DRAM cycles and edge energies all halve
    exactly at 1-byte words; MAC energy and compute cycles don't move."""
    wl = spmm("wb_wl", 32, 64, 48, 0.2, 0.5)
    wide, narrow = _quant_twin(2.0), _quant_twin(1.0)
    rep_w, rep_n = [], []
    for arch in (wide, narrow):
        spec = GenomeSpec(wl, arch=arch)
        g = np.zeros(spec.length, dtype=np.int64)
        for k, v in fixed_mapping_genes_for_arch(spec, arch).items():
            g[k] = v
        rep = evaluate(spec.decode(g), arch)
        assert rep.valid, rep.reason
        (rep_w if arch is wide else rep_n).append(rep)
    rw, rn = rep_w[0], rep_n[0]
    for store, occ in rw.occupancy_bytes.items():
        assert rn.occupancy_bytes[store] == pytest.approx(occ / 2)
    for key, b in rw.traffic_bytes.items():
        assert rn.traffic_bytes[key] == pytest.approx(b / 2)
    assert rn.dram_cycles == pytest.approx(rw.dram_cycles / 2)
    assert rn.compute_cycles == rw.compute_cycles
    assert rn.energy_breakdown["mac"] == rw.energy_breakdown["mac"]
    for grp in ("dram", "glb", "pebuf", "reg"):
        assert rn.energy_breakdown[grp] == \
            pytest.approx(rw.energy_breakdown[grp] / 2)


def test_metadata_bits_do_not_scale_with_word_width():
    """Compression metadata is width-independent, so at narrower words
    the compressed-to-dense byte ratio is WORSE (compression pays off
    later) — the quantized-edge design story."""
    from repro.core.sparse import TensorFormat, effective_bytes
    fmt = TensorFormat("P", formats=(1,), fiber_lens=(64,))   # bitmask
    dense2 = effective_bytes(fmt, 0.1, 64, 2.0) / (64 * 2.0)
    dense1 = effective_bytes(fmt, 0.1, 64, 1.0) / (64 * 1.0)
    assert dense1 > dense2


def test_word_width_topology_and_compilation_sharing():
    """Custom widths split the topology from the default-width kernel
    (the default stays bit-identical), but a FAMILY of custom-width
    specs shares one topology/compilation — widths are traced numbers."""
    assert ARCH_SPARSEMAP.topology.uniform_word_bytes
    q1, q2 = _quant_twin(1.0), _quant_twin(0.5)
    assert not q1.topology.uniform_word_bytes
    assert q1.topology != ARCH_SPARSEMAP.topology
    assert q1.topology == q2.topology
    wl = spmm("wb_sig", 16, 16, 16, 0.5, 0.5)
    m1 = JaxCostModel(GenomeSpec(wl, arch=q1), q1)
    m2 = JaxCostModel(GenomeSpec(wl, arch=q2), q2)
    assert m1.signature == m2.signature
    assert m1.signature != \
        JaxCostModel(GenomeSpec(wl), ARCH_SPARSEMAP).signature
    # param vector tail carries the per-edge widths
    np.testing.assert_allclose(q1.param_vector()[-q1.n_edges:],
                               [1.0] * q1.n_edges)


def test_word_bytes_validation():
    with pytest.raises(ValueError):
        ArchSpec("bad_wb", (
            StorageLevel("dram"),
            StorageLevel("glb", word_bytes=0.0,
                         fill_energy=(("dram", (100.0,)),)),
        ))


# ------------------------------------- new archs: oracle-kernel + e2e


@pytest.mark.parametrize("arch", [SYSTOLIC_MESH, QUANT_EDGE],
                         ids=lambda a: a.name)
def test_new_arch_default_design_oracle_matches_kernel(arch):
    """The engineer-default design is valid on both new topologies and
    the generic numpy oracle agrees with the generic kernel on it (the
    capacity-aware fallback makes this non-vacuous)."""
    wl = spmm("nw_probe", 32, 64, 48, 0.2, 0.5)
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


@pytest.mark.parametrize("archname", ["systolic_mesh", "quant_edge"])
def test_method_sweep_end_to_end_on_noc_word_archs(archname):
    """Acceptance criterion: the systolic-mesh and 1-byte-word
    topologies search end-to-end through the mega-batched sweep at 1.0
    dispatches/round per signature."""
    wls = [spmm(f"{archname}_a", 32, 64, 48, 0.2, 0.5),
           spmm(f"{archname}_b", 48, 32, 64, 0.4, 0.3)]
    stats: dict = {}
    grid = search.run_method_sweep(
        ["sparsemap", "random_mapper"], wls, archname,
        budget=200, seed=0, stats_out=stats)
    arch = search._platform(archname)
    for m in grid:
        for w, res in grid[m].items():
            assert res.evals >= 200
    assert len(stats["signatures"]) == 1
    assert stats["signatures"][0][2] == arch.topology.fingerprint
    assert stats["dispatches"] == stats["rounds"]


def test_sparsemap_finds_valid_designs_on_noc_word_archs():
    wl = spmm("nw_valid", 32, 64, 48, 0.2, 0.5)
    for archname in ("systolic_mesh", "quant_edge"):
        res = search.run("sparsemap", wl, archname, budget=800, seed=0)
        assert np.isfinite(res.best_edp), archname
        rep = search.report_best(wl, archname, res)
        assert rep is not None and rep.valid
        assert rep.edp == pytest.approx(res.best_edp, rel=1e-3)


def test_registered_topologies_are_distinct():
    fps = {a.topology.fingerprint
           for a in (ARCH_SPARSEMAP, MAPLE_EDGE, CLUSTER_CLOUD,
                     SYSTOLIC_MESH, QUANT_EDGE, EYERISS_LIKE,
                     SIGMA_LIKE, DSTC_LIKE)}
    assert len(fps) == 8


# ------------------------------------------- capacity-aware fallback


def test_fallback_mapping_is_valid_on_cluster_cloud_large_workload():
    """Regression: the fixed greedy caps (16/8/64) overflow
    cluster_cloud's 1 MB cluster buffer on large workloads (a 64-per-dim
    staging tile at L3_T alone holds multi-MB P tiles); capacity-aware
    sizing must keep the fallback ``evaluate``-valid."""
    wl = spmm("cc_big", 512, 4096, 512, 0.1, 0.1)
    for arch in (CLUSTER_CLOUD, ARCH_SPARSEMAP, MAPLE_EDGE):
        spec = GenomeSpec(wl, arch=arch)
        g = np.zeros(spec.length, dtype=np.int64)
        for k, v in fixed_mapping_genes_for_arch(spec, arch).items():
            g[k] = v
        rep = evaluate(spec.decode(g), arch)
        assert rep.valid, f"{arch.name}: {rep.reason}"


def test_fallback_mapping_is_valid_on_tiny_buffers():
    """A deliberately starved variant (4 KB GLB, 128 B PE buffers): every
    prime the greedy caps would place on-chip must flow outward
    instead."""
    tiny = ArchSpec("tiny_buffers", (
        StorageLevel("dram"),
        StorageLevel("glb", capacity_bytes=4 * 1024,
                     fill_energy=(("dram", (100.0,)),), sg_site="L2"),
        StorageLevel("pebuf", capacity_bytes=128,
                     fill_energy=(("glb", (3.0, 0.3)),),
                     fanout=16, sg_site="L3"),
        StorageLevel("reg", fill_energy=(("pebuf", (0.6,)),), fanout=4),
    ))
    wl = spmm("tiny_wl", 128, 256, 128, 0.3, 0.3)
    spec = GenomeSpec(wl, arch=tiny)
    g = np.zeros(spec.length, dtype=np.int64)
    for k, v in fixed_mapping_genes_for_arch(spec, tiny).items():
        g[k] = v
    rep = evaluate(spec.decode(g), tiny)
    assert rep.valid, rep.reason
    # ... and the mapping still parallelizes where capacity allows
    mp = balanced_mapping_for_arch(wl, tiny)
    assert any(mp.spatial_fanout(l) > 1 for l in tiny.spatial_levels)


def test_fallback_unchanged_where_capacity_never_binds():
    """On the paper platforms the capacity guard must be a no-op: the
    golden fixed-seed searches depend on these exact seed mappings."""
    from repro.core import accel
    from repro.core.arch import arch_from_platform
    wl = spmm("np_probe", 128, 1024, 128, 0.006, 0.006)
    arch = arch_from_platform(accel.CLOUD)
    mp = balanced_mapping_for_arch(wl, arch)
    # the documented greedy outcome: 16-wide contraction dot product,
    # 16x16 output parallelism, 8-per-dim local tiles
    assert mp.factors[4].get("K", 1) == 16
    assert mp.factors[2].get("M", 1) == 16
    assert mp.factors[2].get("N", 1) == 16
    assert mp.factors[3].get("M", 1) == 8


# ----------------------------------------------- pinned arch goldens


GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "arch_reports_golden.json")


def test_nondefault_arch_cost_reports_match_goldens():
    """CostReport energy_breakdown / occupancy_bytes / cycles for
    maple_edge, cluster_cloud and the zoo entries, pinned as float hex
    on deterministic designs (engineer default, the manual sparse
    strategy, gate-both)."""
    from repro.core.workload import spconv
    gold = json.load(open(GOLDEN))
    wls = {
        "mm_small": spmm("mm_small", 32, 64, 48, 0.2, 0.5),
        "mm_sparse": spmm("mm_sparse", 128, 1024, 128, 0.006, 0.006),
        "conv": spconv("conv", 64, 32, 32, 256, 1, 1, 0.45, 0.252),
    }
    seen = 0
    for arch in (MAPLE_EDGE, CLUSTER_CLOUD, EYERISS_LIKE, SIGMA_LIKE,
                 DSTC_LIKE):
        for wname, wl in wls.items():
            spec = GenomeSpec(wl, arch=arch)
            g0 = np.zeros(spec.length, dtype=np.int64)
            for k, v in fixed_mapping_genes_for_arch(spec, arch).items():
                g0[k] = v
            g1 = g0.copy()
            for k, v in manual_sparse_genes(spec).items():
                g1[k] = v
            g2 = g0.copy()
            g2[spec.segments["sg"].stop - 1] = SG_GATE_BOTH
            for gname, g in (("default", g0), ("manual_sparse", g1),
                             ("gate_both", g2)):
                exp = gold[f"{arch.name}:{wname}:{gname}"]
                rep = evaluate(spec.decode(g), arch)
                assert rep.valid == exp["valid"], \
                    f"{arch.name}:{wname}:{gname}: {rep.reason}"
                assert rep.reason == exp["reason"]
                for bkey, hexval in exp["energy_breakdown"].items():
                    assert rep.energy_breakdown[bkey].hex() == hexval, \
                        f"{arch.name}:{wname}:{gname}: {bkey} drifted"
                for skey, hexval in exp["occupancy_bytes"].items():
                    assert rep.occupancy_bytes[skey].hex() == hexval
                if rep.valid:
                    assert rep.cycles.hex() == exp["cycles"]
                    assert rep.energy_pj.hex() == exp["energy_pj"]
                    assert rep.edp.hex() == exp["edp"]
                seen += 1
    assert seen == len(gold) == 45
