"""Compile the main path's kernels for one TPU v5e chip, without a chip.

The TPU compiler is installed with JAX and compiles for a topology that
is described rather than attached, so these tests catch what only the
chip's compiler refuses (tiling, fast-memory limits, unsupported ops) at
no chip time.  They run nothing: a compile that passes here says nothing
about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and the tests of this file must be collected identically by every test
worker.  The persistent compilation cache is off around these compiles
(an entry written for a described chip cannot be read back here).
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs.paper_workloads import by_name
from repro.core import jax_cost, search

# (workload, arch) pairs of the main path: a 2:4-structured SpMM on the
# paper's cloud accelerator and a SpConv on the Eyeriss-like topology
PAIRS = [("mm9", "cloud"), ("conv4", "eyeriss_like")]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield t
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, structs):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        structs)


def _compile_job(job, sharding):
    _, fn, structs = job
    return fn.lower(*_on(sharding, structs)).compile()


@pytest.mark.parametrize("wl,arch", PAIRS, ids=[f"{w}-{a}" for w, a in PAIRS])
def test_stacked_kernel_compiles_for_v5e(one_chip, wl, arch):
    _, ev = search.get_evaluator(by_name(wl), arch)
    compiled = _compile_job(jax_cost.stacked_compile_job(ev, 2048),
                            one_chip)
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("wl,arch", PAIRS, ids=[f"{w}-{a}" for w, a in PAIRS])
def test_scan_kernel_compiles_for_v5e(one_chip, wl, arch):
    _, ev = search.get_evaluator(by_name(wl), arch)
    job = jax_cost.scan_compile_job(ev, 100, 8, 50, 10, 4, 4)
    compiled = _compile_job(job, one_chip)
    assert compiled.memory_analysis() is not None


def test_bsr_spmm_compiles_to_a_tpu_kernel(one_chip):
    """4096^3 block-sparse SpMM over 128x128 blocks, 128 of them stored
    (4 per block row): the Pallas kernel lowers to a Mosaic custom call."""
    from repro.kernels.bsr_spmm import bsr_spmm
    n, b, nnz = 4096, 128, 128
    m_blocks = n // b
    S = jax.ShapeDtypeStruct
    args = _on(one_chip, (S((nnz, b, b), jnp.bfloat16), S((nnz,), jnp.int32),
                          S((m_blocks + 1,), jnp.int32),
                          S((n, n), jnp.bfloat16)))

    def f(blocks, col_idx, row_ptr, q):
        return bsr_spmm(blocks, col_idx, row_ptr, q, m_blocks=m_blocks,
                        max_row_nnz=nnz // m_blocks, bn=b)

    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
