"""The declarative arch frontend (repro.core.arch_dsl): unit parsers,
exact lowering to hand-built ArchSpecs, the paper topology re-derived
through the DSL bit-identical to the pinned pre-refactor goldens, and
the error surface."""
import os

import numpy as np
import pytest

from repro.core import accel
from repro.core.arch import (ARCH_SPARSEMAP, ArchSpec, NoCSpec,
                             StorageLevel, arch_from_platform)
from repro.core.arch_dsl import (compile_arch, parse_bandwidth,
                                 parse_capacity, parse_frequency,
                                 sparsemap_desc)
from repro.core.encoding import GenomeSpec
from repro.core.jax_cost import JaxCostModel
from repro.core.workload import spmm

# ------------------------------------------------------------- parsers


def test_capacity_strings_are_binary():
    assert parse_capacity("512B") == 512
    assert parse_capacity("256KB") == 256 * 1024
    assert parse_capacity("64MB") == 64 * 1024 ** 2
    assert parse_capacity("2GB") == 2 * 1024 ** 3
    assert parse_capacity(108 * 1024) == 108 * 1024
    with pytest.raises(ValueError):
        parse_capacity("256 potatoes")
    with pytest.raises(ValueError):
        parse_capacity("KB")


def test_bandwidth_strings_are_decimal_rates_per_clock():
    # the configs' own spelling of Table II's starved edge DRAM:
    # 16 MB/s at a 1 GHz clock = 16e6 / 1e9 bytes per cycle, exactly
    assert parse_bandwidth("16MB/s", 1.0e9) == 16e6 / 1.0e9
    assert parse_bandwidth("128GB/s", 1.0e9) == 128e9 / 1.0e9
    assert parse_bandwidth("900GB/s", 2.0e9) == 900e9 / 2.0e9
    assert parse_bandwidth(0.016, 1.0e9) == 0.016   # already per-cycle
    with pytest.raises(ValueError):
        parse_bandwidth("16MB", 1.0e9)              # rate needs /s


def test_frequency_strings():
    assert parse_frequency("1GHz") == 1e9
    assert parse_frequency("200MHz") == 2e8
    assert parse_frequency(5e8) == 5e8


# ------------------------------------------------------------ lowering


def test_compiled_arch_equals_hand_built():
    """DSL lowering is exact: the declarative description of the 4-store
    clustered chip compares equal (content hash and all) to the
    hand-assembled ArchSpec."""
    hand = ArchSpec("dsl_twin", (
        StorageLevel("dram"),
        StorageLevel("glb", capacity_bytes=64 * 1024 * 1024,
                     fill_energy=(("dram", (100.0,)),), sg_site="L2",
                     fill_bandwidth_bytes_per_cycle=128e9 / 1.0e9),
        StorageLevel("cbuf", capacity_bytes=1024 * 1024,
                     fill_energy=(("glb", (15.0, 0.3)),),
                     fanout=16, sg_site="L3"),
        StorageLevel("reg",
                     fill_energy=(("cbuf", (0.5,)), ("reg", (0.05,))),
                     fanout=64),
    ), e_mac=0.8)
    dsl = compile_arch({
        "name": "dsl_twin",
        "levels": [
            {"name": "dram"},
            {"name": "glb", "capacity": "64MB",
             "energy": [["dram", [100.0]]],
             "sg_site": "L2", "bandwidth": "128GB/s"},
            {"name": "cbuf", "capacity": "1MB",
             "energy": [["glb", [15.0, 0.3]]],
             "fanout": 16, "sg_site": "L3"},
            {"name": "reg",
             "energy": [["cbuf", [0.5]], ["reg", [0.05]]],
             "fanout": 64},
        ],
    })
    assert dsl == hand
    assert hash(dsl) == hash(hand)
    np.testing.assert_array_equal(dsl.param_vector(),
                                  hand.param_vector())


def test_all_none_schemes_normalize_to_booleans():
    """'all'/'none' spellings lower to the plain boolean NoCSpec, so a
    desc-built arch is indistinguishable from a hand-built one."""
    dsl = compile_arch({
        "name": "norm", "levels": [
            {"name": "dram"},
            {"name": "glb", "energy": [["dram", [100.0]]],
             "fanout": 4,
             "noc": {"multicast": "none", "reduction": "all"}},
        ]})
    assert dsl.levels[1].noc == NoCSpec(multicast=False, reduction=True)


def test_mesh_fanout_resolves_row_col_discounts():
    """[rows, cols] mesh: total fanout rows*cols; a row-wise bus serves
    `cols` instances per copy, a column-wise one `rows`."""
    dsl = compile_arch({
        "name": "mesh", "levels": [
            {"name": "dram"},
            {"name": "pe", "energy": [["dram", [10.0]]],
             "fanout": [12, 14],
             "noc": {"multicast": "row", "reduction": "col"}},
        ]})
    lv = dsl.levels[1]
    assert lv.fanout == 12 * 14
    assert lv.noc == NoCSpec(multicast="row", reduction="col",
                             multicast_fanout=14.0, reduction_fanout=12.0)


def test_explicit_scheme_fanout_pair():
    dsl = compile_arch({
        "name": "pair", "levels": [
            {"name": "dram"},
            {"name": "pe", "energy": [["dram", [10.0]]],
             "fanout": 64,
             "noc": {"reduction": ["cluster", 8]}},
        ]})
    assert dsl.levels[1].noc == NoCSpec(
        reduction="cluster", reduction_fanout=8.0)


# ------------------------------------------------- the paper topology


def test_sparsemap_desc_equals_hand_built_on_all_platforms():
    for name, plat in accel.PLATFORMS.items():
        assert compile_arch(sparsemap_desc(name)) == \
            arch_from_platform(plat), name
    assert compile_arch(sparsemap_desc("cloud", name="sparsemap")) == \
        ARCH_SPARSEMAP


GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "arch_sparsemap_golden.npz")
#: the kernel's float32 columns in the golden captures are held to 4 ULP
#: (``valid`` and the float64 oracle stay exact): the pin is on what the
#: model means, not on one XLA release's bits (tests/golden/README.md)
GOLDEN_F32_RTOL = 4.8e-7


def test_dsl_rebuilt_paper_arch_matches_goldens_bit_for_bit():
    """The acceptance pin: ARCH_SPARSEMAP rebuilt through the frontend
    reproduces the pre-refactor golden kernel outputs (captured genome
    batches, one workload x all platforms): ``valid`` exactly, the
    float32 columns to :data:`GOLDEN_F32_RTOL`."""
    g = np.load(GOLDEN)
    wl = spmm("mm_small", 32, 64, 48, 0.2, 0.5)
    for pname in accel.PLATFORMS:
        arch = compile_arch(sparsemap_desc(pname))
        key = f"{wl.name}:{pname}"
        res = JaxCostModel(GenomeSpec(wl, arch=arch), arch)(
            g[f"{key}:genomes"])
        np.testing.assert_array_equal(
            g[f"{key}:jax_valid"], np.asarray(res["valid"]),
            err_msg=f"{key}: valid drifted through the DSL")
        for fld, out_key in (("jax_edp", "edp"),
                             ("jax_energy", "energy_pj"),
                             ("jax_cycles", "cycles")):
            np.testing.assert_allclose(
                np.asarray(res[out_key]), g[f"{key}:{fld}"],
                rtol=GOLDEN_F32_RTOL, atol=0,
                err_msg=f"{key}: {out_key} drifted through the DSL")


# --------------------------------------------------------------- errors


@pytest.mark.parametrize("desc, fragment", [
    ({"levels": []}, "needs a 'name'"),
    ({"name": "x"}, "needs a 'levels'"),
    ({"name": "x", "levels": [{"name": "d"}], "junk": 1},
     "unknown description keys"),
    ({"name": "x", "levels": [{"name": "d", "typo_key": 1},
                              {"name": "g",
                               "energy": [["d", [1.0]]]}]},
     "unknown keys"),
    ({"name": "x", "levels": [{"name": "d", "capacity": "1KB"},
                              {"name": "g",
                               "energy": [["d", [1.0]]]}]},
     "outermost"),
    ({"name": "x", "levels": [{"name": "d"},
                              {"name": "g", "energy": 3.0}]},
     "energy must be ordered"),
    ({"name": "x", "levels": [{"name": "d"},
                              {"name": "g", "energy": [["d", [1.0]]],
                               "fanout": [2, 3, 4]}]},
     "[rows, cols]"),
    ({"name": "x", "levels": [{"name": "d"},
                              {"name": "g", "energy": [["d", [1.0]]],
                               "noc": {"multicast": "row"}}]},
     "mesh"),
    ({"name": "x", "levels": [{"name": "d"},
                              {"name": "g", "energy": [["d", [1.0]]],
                               "noc": {"multicast": "cluster"}}]},
     "explicit discount"),
    ({"name": "x", "levels": [{"name": "d"},
                              {"name": "g", "energy": [["d", [1.0]]],
                               "noc": {"reduction": ["all", 4]}}]},
     "takes no fanout"),
    ({"name": "x", "levels": [{"name": "d"},
                              {"name": "g", "energy": [["d", [1.0]]],
                               "noc": {"wrong": True}}]},
     "unknown noc keys"),
])
def test_description_errors(desc, fragment):
    with pytest.raises(ValueError) as ei:
        compile_arch(desc)
    assert fragment in str(ei.value)
