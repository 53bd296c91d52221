"""Pipeline parallelism: GPipe fill-drain over a 'pipe' mesh axis.

Operates on exactly the shape our backbone already has — a scanned
per-layer body with stacked parameters.  Layers are split into
`n_stages` contiguous stages (stacked params sharded on the leading
layer dim over the 'pipe' axis); microbatches stream through stages
with `jax.lax.ppermute` handing activations to the next stage.

Inside shard_map each device runs `steps = n_micro + n_stages - 1`
iterations (fill + steady state + drain); stage s computes on iteration
t the microbatch m = t - s when 0 <= m < n_micro.  Differentiable:
jax.grad flows through ppermute (its transpose is the reverse permute),
giving 1F1B-equivalent compute with GPipe scheduling.

The production (16,16)/(2,16,16) meshes use DP x TP; PP is exercised on
auxiliary meshes (tests use a 4-device 'pipe' mesh) and composes with
the same body functions — see tests/test_pipeline.py.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


def pipeline_apply(
    body: Callable,  # (layer_params, x) -> x, one layer
    stacked_params,  # leaves [L, ...]
    x: Array,        # [n_micro, mb, ...] microbatched activations
    mesh: Mesh,
    axis: str = "pipe",
):
    """Run L = n_stages*layers_per_stage layers over microbatches."""
    n_stages = mesh.shape[axis]
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    assert L % n_stages == 0, (L, n_stages)
    n_micro = x.shape[0]

    def stage_fn(params_stage, xs):
        # params_stage: leaves [L/n_stages, ...] (this stage's layers)
        # xs: [n_micro, mb, ...] (only stage 0 reads real inputs)
        idx = lax.axis_index(axis)
        steps = n_micro + n_stages - 1
        mb_shape = xs.shape[1:]
        buf = jnp.zeros((n_micro,) + mb_shape, xs.dtype)  # outputs (last stage)

        def apply_stage(x):
            def layer(x, lp):
                return body(lp, x), None
            x, _ = lax.scan(layer, x, params_stage)
            return x

        def step(carry, t):
            buf, cur = carry
            m = t - idx  # microbatch index at this stage
            # stage 0 injects fresh microbatch m = t
            inject = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(idx == 0, xs[inject], cur)
            active = (m >= 0) & (m < n_micro)
            y = jnp.where(active, apply_stage(x_in), x_in)
            # last stage records its finished microbatch
            buf = jnp.where(
                (idx == n_stages - 1) & active,
                lax.dynamic_update_index_in_dim(
                    buf, y, jnp.clip(m, 0, n_micro - 1), 0
                ),
                buf,
            )
            # hand activations to the next stage
            nxt = lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (buf, nxt), None

        # initial carry must be marked varying over the pipe axis (each
        # stage's carry evolves independently between ppermutes)
        init = jax.tree.map(
            lambda a: lax.pcast(a, (axis,), to="varying"),
            (buf, jnp.zeros(mb_shape, xs.dtype)),
        )
        (buf, _), _ = lax.scan(step, init, jnp.arange(steps))
        # broadcast the last stage's outputs to all stages (masked psum:
        # ppermute requires unique sources, one-to-all is a reduction)
        out = lax.psum(
            jnp.where(idx == n_stages - 1, buf, jnp.zeros_like(buf)), axis
        )
        return out

    pspec_params = jax.tree.map(lambda _: P(axis), stacked_params)
    fn = jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(pspec_params, P()),
        out_specs=P(),
    )
    return fn(stacked_params, x)


def make_pp_loss(body, n_micro: int):
    """Loss over the pipelined stack (for tests / PP training demos)."""

    def loss_fn(stacked_params, x, targets, mesh):
        y = pipeline_apply(body, stacked_params, x, mesh)
        return jnp.mean(jnp.square(y - targets))

    return loss_fn
