"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Each device along the ``pipe`` mesh axis holds ONE stage's weights; micro-
batches stream through the stages with ``jax.lax.ppermute`` hops — the
standard JAX-native pipeline (MaxText-style), usable as an outer level on
top of the (data, model) mesh for cross-pod scaling where DP bandwidth is
the constraint.

The schedule is the classic GPipe fill-drain: T = n_micro + n_stages - 1
ticks; device s computes microbatch m at tick t = m + s.  Bubble fraction
= (n_stages-1)/T, so callers should use n_micro >> n_stages.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from jax import shard_map


def pipeline_apply(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stage_params: Any, x: jnp.ndarray, mesh: Mesh,
                   axis: str = "pipe") -> jnp.ndarray:
    """Run ``x`` through ``n_stages`` pipelined applications of
    ``stage_fn``.

    stage_params: pytree with leading axis n_stages (sharded over
    ``axis``); x: [n_micro, mb, ...] microbatched input (replicated).
    Returns [n_micro, mb, ...] outputs of the LAST stage.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    T = n_micro + n_stages - 1

    pspec = jax.tree.map(lambda _: P(axis), stage_params)

    def body(params, xs):
        params = jax.tree.map(lambda t: t[0], params)   # local stage
        stage = jax.lax.axis_index(axis)
        carry = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(t, state):
            carry, outs = state
            m_in = t                        # microbatch entering stage 0
            feed = xs[jnp.clip(m_in, 0, n_micro - 1)]
            inp = jnp.where(stage == 0, feed, carry)
            out = stage_fn(params, inp)
            # last stage writes its finished microbatch m = t - (S-1)
            m_out = t - (n_stages - 1)
            valid = (m_out >= 0) & (m_out < n_micro)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, jnp.where(stage == n_stages - 1, out,
                                 o[jnp.clip(m_out, 0, n_micro - 1)]),
                    jnp.clip(m_out, 0, n_micro - 1), 0),
                lambda o: o, outs)
            carry = jax.lax.ppermute(out, axis, perm)
            return carry, outs

        carry, outs = jax.lax.fori_loop(0, T, tick, (carry, outs))
        # gather the last stage's outputs to all pipeline ranks
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    fn = shard_map(body, mesh=mesh,
                   in_specs=(pspec, P()), out_specs=P(),
                   check_vma=False)
    return fn(stage_params, x)
