"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

Stages are contiguous layer blocks whose stacked parameters are sharded over
the pipeline axis; activations hop stage->stage with ``ppermute`` inside a
``shard_map``. The schedule is the classic lock-step GPipe wavefront:
``n_micro + n_stages - 1`` ticks, each device computing (or idling through)
one microbatch per tick — bubbles are real and show up in the tick count,
exactly like on hardware.

This composes with the rest of the framework as the PP building block of
DESIGN.md §4 (e.g. "model" or a dedicated "pp" axis as the pipeline axis,
DP on the remaining axes).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def pipeline_apply(mesh: Mesh, axis: str, stage_fn: Callable,
                   stage_params, x_micro: jax.Array) -> jax.Array:
    """Run ``n_stages`` pipeline stages over ``n_micro`` microbatches.

    stage_fn(params_slice, x) -> y        (same shape as x)
    stage_params: pytree with leading dim n_stages (sharded over ``axis``)
    x_micro: [n_micro, mb, ...] (replicated along ``axis``)
    returns [n_micro, mb, ...] — the last stage's outputs.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    p_specs = jax.tree.map(lambda _: P(axis), stage_params)

    def local(p_local, xs):
        # p_local leaves have leading dim 1 (this device's stage)
        p_stage = jax.tree.map(lambda a: a[0], p_local)
        stage_id = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            recv, outputs = carry
            m = t - stage_id                    # microbatch at this stage now
            valid = (m >= 0) & (m < n_micro)
            # stage 0 reads from the input stream; others from recv
            x0 = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(m, 0, n_micro - 1), 0, keepdims=False)
            x_in = jnp.where(stage_id == 0, x0, recv)
            y = stage_fn(p_stage, x_in)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            # last stage writes its finished microbatch into the output
            write = valid & (stage_id == n_stages - 1)
            outputs = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(m, 0, n_micro - 1), 0),
                lambda o: o, outputs)
            # hop the activation to the next stage
            nxt = jax.lax.ppermute(y, axis, perm_fwd)
            return (nxt, outputs), None

        init = (jnp.zeros(mb_shape, xs.dtype),
                jnp.zeros((n_micro,) + mb_shape, xs.dtype))
        (recv, outputs), _ = jax.lax.scan(
            tick, init, jnp.arange(n_micro + n_stages - 1))
        # only the last stage holds real outputs; gather + select them so the
        # result is replicated (out_specs P())
        return jax.lax.all_gather(outputs, axis)[n_stages - 1]

    fn = shard_map(local, mesh=mesh,
                   in_specs=(p_specs, P()),
                   out_specs=P(),
                   check_vma=False)
    return fn(stage_params, x_micro)


def pipeline_bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble overhead: (S-1) / (M + S - 1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
