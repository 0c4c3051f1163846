"""Device-resident ES rounds (COMPAT.md "Device-resident round
protocol"): fixed-seed parity pins between the three execution paths of
a ``device_rounds=k`` fleet —

* **device**: k generations folded into one vmap-of-``lax.scan`` program
  (``jax_cost.run_segments``), host sync once per segment;
* **host-loop**: the same generator answered with ``None``, replaying
  the identical pre-drawn operator plan per-round on the host;
* **legacy k=1**: the original per-generation loop.

Device and host-loop consume the same ``DeviceSegment.draws``, so they
must match BIT-FOR-BIT (best EDP and the full history curve).  Legacy
k=1 differs from k>1 in exactly ONE seam — the legacy loop sorts fitness
with numpy's unstable introsort while segment selection is stable — and
that seam is pinned here explicitly: forcing unstable tie order into the
segment path reproduces the legacy trajectory bit-for-bit.  The
numpy-vs-threefry RNG seam is pinned the same way (deterministic, but a
different stream by construction).
"""
import numpy as np
import pytest

from repro.configs.paper_workloads import by_name
from repro.core import es_ops, search
from repro.core.es_ops import PaddedLayout

BUDGET = 700
SEED = 3
K = 4


def _grid_equal(a, b):
    """Bit-exact best-EDP + history equality over two result grids."""
    assert set(a) == set(b)
    for m in a:
        assert set(a[m]) == set(b[m])
        for w in a[m]:
            ra, rb = a[m][w], b[m][w]
            assert ra.best_edp == rb.best_edp, (m, w)
            assert np.array_equal(ra.history, rb.history), (m, w)
            assert ra.evals == rb.evals and \
                ra.valid_evals == rb.valid_evals, (m, w)


def _sweep(methods, wls, arch, device_execute, device_rounds=K,
           stats=None, method_kw=None):
    return search.run_method_sweep(
        methods, [by_name(w) for w in wls], arch, budget=BUDGET,
        seed=SEED, device_rounds=device_rounds,
        device_execute=device_execute,
        stats_out=stats if stats is not None else {},
        method_kw=method_kw)


# ------------------------------------------------ device == host-loop


@pytest.mark.parametrize("arch", ["cloud", "maple_edge"])
def test_device_segments_match_host_loop_bitwise(arch):
    stats_dev, stats_host = {}, {}
    dev = _sweep(["sparsemap"], ["mm1", "mm3"], arch, True,
                 stats=stats_dev)
    host = _sweep(["sparsemap"], ["mm1", "mm3"], arch, False,
                  stats=stats_host)
    _grid_equal(dev, host)
    # the device fleet folded k generations per host sync; the host-loop
    # reference paid one sync per generation
    assert stats_dev["host_syncs_per_round"] <= 1 / K
    assert stats_host["host_syncs_per_round"] >= 1.0
    assert stats_dev["host_syncs"] < stats_host["host_syncs"]


def test_mixed_density_mixed_method_fleet_parity():
    # uniform (mm1) + block-N:M structured (mm8) workloads promote the
    # fleet onto the structured kernel; standard_es has no device path
    # and must ride along unchanged
    methods = ["sparsemap", "standard_es"]
    wls = ["mm1", "mm8"]
    dev = _sweep(methods, wls, "cloud", True)
    host = _sweep(methods, wls, "cloud", False)
    _grid_equal(dev, host)
    # standard_es is per-round in ALL modes: identical to a k=1 fleet
    k1 = _sweep(methods, wls, "cloud", True, device_rounds=1)
    _grid_equal({"standard_es": dev["standard_es"]},
                {"standard_es": k1["standard_es"]})


# ------------------------------------------------ the k=1 <-> k>1 seam


def test_sort_stability_is_the_only_k1_seam(monkeypatch):
    """Legacy k=1 vs segmented k>1 differ ONLY in selection tie order
    (unstable introsort vs stable sort).  With unstable order forced
    into the segment path, the k>1 host-loop reproduces the legacy
    trajectory bit-for-bit."""
    wl = by_name("mm1")
    legacy = search.run("sparsemap", wl, "cloud", budget=BUDGET,
                        seed=SEED)
    from repro.core import evolution
    monkeypatch.setattr(evolution.es_ops, "stable_order",
                        lambda edp: np.argsort(edp))
    seg = _sweep(["sparsemap"], ["mm1"], "cloud", False)
    res = seg["sparsemap"]["mm1"]
    assert res.best_edp == legacy.best_edp
    assert np.array_equal(res.history, legacy.history)


def test_threefry_backend_deterministic_and_distinct():
    kw = {"sparsemap": dict(rng_backend="threefry")}
    dev = _sweep(["sparsemap"], ["mm1"], "cloud", True, method_kw=kw)
    host = _sweep(["sparsemap"], ["mm1"], "cloud", False, method_kw=kw)
    _grid_equal(dev, host)        # device RNG is driver-invariant too
    # ... but a different stream from the numpy oracle (the RNG seam):
    # same budget/seed, different draws -> different history
    numpy_dev = _sweep(["sparsemap"], ["mm1"], "cloud", True)
    assert not np.array_equal(dev["sparsemap"]["mm1"].history,
                              numpy_dev["sparsemap"]["mm1"].history)
    assert dev["sparsemap"]["mm1"].evals == \
        numpy_dev["sparsemap"]["mm1"].evals


# ------------------------------------------------ operator unit pins


def test_apply_ops_numpy_jnp_equal():
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    n, L, genes_per = 33, 17, 2
    gene_ub = rng.integers(2, 9, L)
    parents = rng.integers(0, 2, (7, L)).astype(np.int64)
    cut_arr = es_ops.crossover_cut_points(L)
    plan = es_ops.plan_generation(
        rng, n_children=n, n_parents=len(parents), cut_arr=cut_arr,
        gene_ub=gene_ub, genes_per=genes_per, p_mut=0.9, p_high=0.5,
        hi=None, lo=None)
    kids_np = es_ops.apply_crossover(parents, plan.ab, plan.cuts)
    kids_j = es_ops.apply_crossover(jnp.asarray(parents),
                                    jnp.asarray(plan.ab),
                                    jnp.asarray(plan.cuts))
    assert np.array_equal(kids_np, np.asarray(kids_j))
    mut_np = es_ops.apply_mutation(kids_np, plan.active, plan.gene,
                                   plan.vals)
    mut_j = es_ops.apply_mutation(jnp.asarray(kids_np),
                                  jnp.asarray(plan.active),
                                  jnp.asarray(plan.gene),
                                  jnp.asarray(plan.vals))
    assert np.array_equal(mut_np, np.asarray(mut_j))
    # duplicate-column overwrite order: force all draws onto one gene
    gene = np.zeros((n, genes_per), dtype=np.int64)
    dup_np = es_ops.apply_mutation(kids_np, plan.active, gene, plan.vals)
    dup_j = es_ops.apply_mutation(jnp.asarray(kids_np),
                                  jnp.asarray(plan.active),
                                  jnp.asarray(gene),
                                  jnp.asarray(plan.vals))
    assert np.array_equal(dup_np, np.asarray(dup_j))
    assert np.array_equal(
        dup_np[plan.active, 0], plan.vals[plan.active, -1])


def test_stable_order_and_best_so_far_backends_agree():
    import jax.numpy as jnp
    edp = np.array([3.0, 1.0, 1.0, np.inf, 2.0, 1.0, np.inf],
                   dtype=np.float32)
    assert np.array_equal(es_ops.stable_order(edp),
                          np.asarray(es_ops.stable_order(jnp.asarray(edp))))
    assert np.array_equal(es_ops.best_so_far(edp),
                          np.asarray(es_ops.best_so_far(jnp.asarray(edp))))


def test_padded_layout_roundtrip_and_index_maps():
    from repro.core.encoding import GenomeSpec
    spec = GenomeSpec(by_name("mm1"))
    lay = PaddedLayout(spec, spec.n_primes + 5)
    rng = np.random.default_rng(1)
    g = spec.random_genomes(rng, 8)
    gp = lay.pad_rows(g)
    assert gp.shape == (8, lay.Lp)
    assert np.array_equal(lay.unpad_rows(gp), g)
    # pad columns are inert zeros
    pad_cols = np.setdiff1d(np.arange(lay.Lp), lay.cols)
    assert (gp[:, pad_cols] == 0).all()
    idx = np.arange(spec.length)
    padded_idx = lay.pad_index(idx)
    # a padded gene index addresses the same gene the canonical one did
    assert np.array_equal(gp[:, padded_idx], g[:, idx])
    # cuts: the canonical prefix is preserved through the map
    for cut in range(1, spec.length):
        pc = int(lay.pad_cut(np.asarray(cut)))
        left = lay.unpad_rows(
            np.pad(gp[:, :pc], ((0, 0), (0, lay.Lp - pc))))
        assert np.array_equal(left[:, :cut], g[:, :cut])


# ------------------------------------------------ forced multi-device

SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro.configs.paper_workloads import by_name
from repro.core import jax_cost, search
from repro.launch.mesh import make_search_mesh

mesh = make_search_mesh()
assert mesh is not None and int(np.asarray(mesh.devices).size) == 8

# 1. sharded mega-batch == single-device mega-batch, bit for bit
spec, ev = search.get_evaluator(by_name("mm1"), "cloud")
rng = np.random.default_rng(0)
batches = [spec.random_genomes(rng, n) for n in (48, 50, 64)]
models = [ev] * len(batches)
plain = jax_cost.eval_stacked(models, batches)
shard = jax_cost.eval_stacked(models, batches, mesh=mesh)
for p, s in zip(plain, shard):
    for k in p:
        assert np.array_equal(p[k], s[k]), k
print("EVAL_STACKED_SHARDED_OK")

# 2. an 8-task segment fleet (task axis divisible by 8 -> sharded scan)
# == the same fleet on one device, bit for bit
def fleet(mesh):
    tasks = [search.SearchTask(by_name("mm1"), "cloud", budget=700,
                               seed=s, name=f"t{s}") for s in range(8)]
    ms = search.MultiSearch(tasks, device_rounds=4, mesh=mesh)
    return ms.run(), ms.stats

res1, st1 = fleet(None)
res8, st8 = fleet(mesh)
assert st8["devices"] == 8 and st1["devices"] == 1
assert st8["host_syncs_per_round"] <= 0.25
for name in res1:
    assert res1[name].best_edp == res8[name].best_edp, name
    assert np.array_equal(res1[name].history, res8[name].history), name
print("SEGMENT_FLEET_SHARDED_OK")
"""


@pytest.mark.slow
def test_forced_multi_device_sharding_matches_single_device(
        subprocess_env):
    import os
    import subprocess
    import sys
    r = subprocess.run(
        [sys.executable, "-c", SHARD_SCRIPT], capture_output=True,
        text=True, timeout=600, env=subprocess_env(),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "EVAL_STACKED_SHARDED_OK" in r.stdout, \
        r.stdout + "\n" + r.stderr
    assert "SEGMENT_FLEET_SHARDED_OK" in r.stdout, \
        r.stdout + "\n" + r.stderr
