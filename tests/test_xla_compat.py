"""Unit tests for the compiled-artifact introspection helper (dict /
None / raising ``cost_analysis()``) and the ``jax.shard_map`` surface
the sharded kernels call."""
import numpy as np
import pytest

from repro.launch.xla_compat import xla_cost_analysis


class _FakeCompiled:
    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        if isinstance(self._ca, Exception):
            raise self._ca
        return self._ca


def test_dict_return_passes_through():
    ca = {"flops": 10.0, "bytes accessed": 4.0}
    out = xla_cost_analysis(_FakeCompiled(ca))
    assert out == ca
    assert out is not ca                       # defensive copy


def test_none_and_errors_give_empty_dict():
    assert xla_cost_analysis(_FakeCompiled(None)) == {}
    assert xla_cost_analysis(
        _FakeCompiled(RuntimeError("unsupported"))) == {}


def test_real_compiled_artifact():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    c = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((16, 16), jnp.float32)).compile()
    out = xla_cost_analysis(c)
    assert isinstance(out, dict)
    assert out.get("flops", 0.0) > 0


def test_shard_map_compat_runs():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("x",))
    with mesh:
        fn = jax.shard_map(lambda a: a * 2.0, mesh=mesh,
                       in_specs=P(), out_specs=P(), check_vma=False)
        y = fn(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(y), [0.0, 2.0, 4.0, 6.0])
