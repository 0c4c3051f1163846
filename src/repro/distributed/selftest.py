"""Multi-device self-test, run in a subprocess with forced host devices
(tests/test_distributed.py): exercises pipeline parallelism, compressed
all-reduce, sharded train-step equivalence, and elastic checkpoint
restore onto a different mesh.  Prints "SELFTEST OK" on success.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from jax import shard_map


def test_pipeline():
    from repro.distributed.pipeline import pipeline_apply
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("pipe",))
    n_stages, n_micro, mb, d = 4, 8, 2, 16
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((n_stages, d, d)) * 0.3,
                    jnp.float32)
    x = jnp.asarray(rng.standard_normal((n_micro, mb, d)), jnp.float32)

    def stage(wi, h):
        return jnp.tanh(h @ wi)

    with mesh:
        y = pipeline_apply(stage, w, x, mesh, axis="pipe")
    # sequential reference
    ref = x
    for s in range(n_stages):
        ref = jnp.tanh(ref @ w[s])
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    print("pipeline ok")


def test_compressed_psum():
    from repro.optim.compression import compressed_psum
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)

    def f(xl):
        return compressed_psum(xl, "data")

    with mesh:
        y = shard_map(f, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"))(x)
    exact = x.sum(axis=0, keepdims=True)
    got = np.asarray(y)[0:1]
    rel = np.abs(got - np.asarray(exact)).max() / \
        np.abs(np.asarray(exact)).max()
    assert rel < 0.02, f"int8 psum rel err {rel}"
    print(f"compressed_psum ok (rel err {rel:.4f})")


def test_sharded_train_step_matches_single():
    """Sharded train step == single-device train step (same batch)."""
    from repro.configs import smoke_config
    from repro.models import sharding as shard_ctx
    from repro.models.model import Model
    from repro.optim import optimizer as opt
    from repro.launch.steps import build_train_step

    cfg = smoke_config("mistral-nemo-12b")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    ocfg = opt.OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    ostate = opt.init(params, ocfg)
    rng = np.random.default_rng(2)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)),
                              jnp.int32)}

    step = build_train_step(m, ocfg)
    p1, o1, m1 = jax.jit(step)(params, ostate, batch)

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))
    shard_ctx.set_batch_axes(("data",))
    try:
        pspecs = m.param_specs()
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                           is_leaf=lambda x: isinstance(x, P))
        bsh = {k: NamedSharding(mesh, P("data", None))
               for k in batch}
        with mesh:
            params_s = jax.device_put(params, psh)
            batch_s = jax.device_put(batch, bsh)
            p2, o2, m2 = jax.jit(step)(params_s, ostate, batch_s)
    finally:
        shard_ctx.set_batch_axes(None)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-2)
    # parameters close after one update
    l1 = jax.tree.leaves(p1)
    l2 = jax.tree.leaves(p2)
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0.1, atol=0.05)
    print(f"sharded train step ok (loss {float(m1['loss']):.4f} vs "
          f"{float(m2['loss']):.4f})")


def test_elastic_restore():
    """Checkpoint on a (2,4) mesh, restore onto (1,4) (mesh shrink)."""
    from repro.checkpoint import checkpoint as ckpt
    from repro.runtime.fault_tolerance import ElasticPlan

    rng = np.random.default_rng(3)
    tree = {"w": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((16,)), jnp.float32)}
    mesh_a = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                  ("data", "model"))
    sh_a = {"w": NamedSharding(mesh_a, P("data", "model")),
            "b": NamedSharding(mesh_a, P("model"))}
    tree_a = jax.device_put(tree, sh_a)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 7, tree_a)
        assert ckpt.latest_step(d) == 7
        mesh_b = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 4),
                      ("data", "model"))
        sh_b = {"w": NamedSharding(mesh_b, P("data", "model")),
                "b": NamedSharding(mesh_b, P("model"))}
        restored = ckpt.restore(d, 7, tree, shardings=sh_b)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        plan = ElasticPlan.plan(n_devices=4, model_parallel=4)
        assert plan.data_parallel == 1
    print("elastic restore ok")


if __name__ == "__main__":
    test_pipeline()
    test_compressed_psum()
    test_sharded_train_step_matches_single()
    test_elastic_restore()
    print("SELFTEST OK")
