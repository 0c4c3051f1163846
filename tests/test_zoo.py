"""The accelerator zoo: published-accelerator design points declared
through the DSL frontend — registry resolution and its error surface,
the pinned published-vs-modeled validation table, and the end-to-end
CI-gated sweeps."""
import json
import os

import numpy as np
import pytest

from repro.configs.archs import (ACCEL_ARCHS, ZOO_ARCHS,
                                 zoo_validation_report)
from repro.core import search
from repro.core.arch import UnknownArchError, as_arch, registered_archs
from repro.core.workload import spmm

VALIDATION = os.path.join(os.path.dirname(__file__), "golden",
                          "zoo_validation.json")


# ------------------------------------------------------------ registry


def test_zoo_entries_are_registered_and_resolvable():
    reg = registered_archs()
    for name, spec in ZOO_ARCHS.items():
        assert reg[name] is spec
        assert as_arch(name) is spec
        assert ACCEL_ARCHS[name] is spec


def test_as_arch_unknown_name_lists_registry():
    """Satellite bugfix pin: an unknown name raises a KeyError SUBCLASS
    (existing callers keep working) whose message enumerates the paper
    platforms and every registered arch, with a close-match hint."""
    with pytest.raises(UnknownArchError) as ei:
        as_arch("eyeris_like")          # sic: one 's'
    msg = str(ei.value)
    assert "eyeris_like" in msg
    assert "did you mean" in msg and "eyeriss_like" in msg
    for expected in ("edge", "mobile", "cloud", "maple_edge",
                     "sigma_like", "dstc_like", "register_arch",
                     "arch_dsl"):
        assert expected in msg, expected
    with pytest.raises(KeyError):       # subclass contract
        as_arch("definitely_not_an_arch")


# ----------------------------------------------------- validation table


def test_zoo_validation_table_matches_published_numbers():
    """Every zoo entry's modeled quantities — recomputed from the
    REGISTERED specs, never read back from the JSON — agree with the
    pinned table: exactly with the pinned 'modeled' column (the
    declarations did not drift) and within each check's tolerance of the
    'published' column (the declarations match the literature)."""
    table = json.load(open(VALIDATION))
    report = zoo_validation_report()
    assert set(table) == set(report) == set(ZOO_ARCHS)
    for arch_name, entry in table.items():
        assert entry["source"], arch_name       # citation is mandatory
        modeled = report[arch_name]
        assert set(entry["checks"]) == set(modeled), arch_name
        for check, pin in entry["checks"].items():
            got = modeled[check]
            assert got == pytest.approx(pin["modeled"], rel=1e-12), \
                f"{arch_name}.{check}: spec drifted from pinned table"
            tol = pin["rel_tol"]
            pub = pin["published"]
            assert abs(got - pub) <= tol * abs(pub) + 1e-9, \
                f"{arch_name}.{check}: modeled {got} vs published {pub}"


# ------------------------------------------------------------------ e2e


@pytest.mark.parametrize("archname", sorted(ZOO_ARCHS))
def test_method_sweep_end_to_end_on_zoo_archs(archname):
    """Acceptance criterion: every zoo entry searches end-to-end through
    the mega-batched sweep at 1.0 dispatches/round per signature."""
    wls = [spmm(f"{archname}_a", 32, 64, 48, 0.2, 0.5),
           spmm(f"{archname}_b", 48, 32, 64, 0.4, 0.3)]
    stats: dict = {}
    grid = search.run_method_sweep(
        ["sparsemap", "random_mapper"], wls, archname,
        budget=200, seed=0, stats_out=stats)
    arch = as_arch(archname)
    for m in grid:
        for w, res in grid[m].items():
            assert res.evals >= 200
    assert len(stats["signatures"]) == 1
    assert stats["signatures"][0][2] == arch.topology.fingerprint
    assert stats["dispatches"] == stats["rounds"]


def test_sparsemap_finds_valid_designs_on_zoo_archs():
    wl = spmm("zoo_valid", 32, 64, 48, 0.2, 0.5)
    for archname in sorted(ZOO_ARCHS):
        res = search.run("sparsemap", wl, archname, budget=400, seed=0)
        assert np.isfinite(res.best_edp), archname
        rep = search.report_best(wl, archname, res)
        assert rep is not None and rep.valid, archname
        assert rep.edp == pytest.approx(res.best_edp, rel=1e-3)
