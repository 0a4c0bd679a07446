"""GPipe-style pipeline parallelism over the slow ("pod") mesh axis.

Motivation: across pods the DCN link is far slower than ICI, so instead of
stretching the DP all-reduce across it, the layer stack can be split into
one stage per pod and microbatches streamed through — cross-pod traffic
becomes O(activations · microbatches) point-to-point instead of
O(params) all-reduce.

Implementation: shard_map over the stage axis; every stage runs the same
scan over T = n_micro + n_stages - 1 ticks:

    tick t: x_in  <- ppermute(+1)(x_out_prev)      # receive from left
            if stage == 0: x_in = microbatch[t]    # inject at the head
            x_out = stage_fn(stage_params, x_in)   # bubble ticks compute
                                                   # garbage, masked later
    outputs: last stage's x_out at ticks >= n_stages - 1

The whole schedule is differentiable (ppermute transposes to the reverse
permute), so training backprops through the pipe — GPipe semantics with
re-forward on the backward pass (remat inside stage_fn).

Microbatch tensors are staged on the FIRST stage only; other stages carry
zeros of the same shape (SPMD requires a uniform program).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    stage_params,                # pytree, leaves (n_stages, ...) sharded on axis
    microbatches: jnp.ndarray,   # (n_micro, mb, ...) replicated
    stage_fn: Callable,          # (params_for_stage, x) -> y (same shape)
    mesh: Mesh,
    axis: str = "pod",
):
    """Returns (n_micro, mb, ...) outputs of the final stage."""
    n_stages = mesh.devices.shape[list(mesh.axis_names).index(axis)]
    n_micro = microbatches.shape[0]
    ticks = n_micro + n_stages - 1
    mb_shape = microbatches.shape[1:]

    def per_stage(params_blk, mbs):
        # params_blk leaves: (1, ...) — this stage's slice
        params_local = jax.tree.map(lambda x: x[0], params_blk)
        stage = jax.lax.axis_index(axis)

        def tick(carry, t):
            x_prev = carry
            # receive from the previous stage (ring shift +1)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            x_in = jax.lax.ppermute(x_prev, axis, perm)
            inject = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False
            )
            x_in = jnp.where(stage == 0, jnp.where(t < n_micro, inject, x_in), x_in)
            x_out = stage_fn(params_local, x_in)
            return x_out, x_out

        x0 = jnp.zeros(mb_shape, microbatches.dtype)
        # the carry is device-varying over the stage axis (shard_map's
        # varying-manual-axes check)
        x0 = jax.lax.pcast(x0, (axis,), to="varying")
        _, ys = jax.lax.scan(tick, x0, jnp.arange(ticks))
        # final-stage outputs live at ticks n_stages-1 .. ticks-1
        out = jax.lax.dynamic_slice_in_dim(ys, n_stages - 1, n_micro, axis=0)
        # broadcast the last stage's result to all stages so out_specs can
        # be replicated (psum of masked contributions). Mask by SELECT, not
        # multiply: non-final stages hold bubble-tick garbage here, and if
        # a stage_fn turns the zero-carry bubble input into NaN/inf then
        # `garbage * 0 = NaN` would poison the real output through the
        # psum — where() never evaluates arithmetic on the untaken branch
        is_last = stage == n_stages - 1
        return jax.lax.psum(jnp.where(is_last, out, jnp.zeros_like(out)),
                            axis)

    pspecs = jax.tree.map(lambda _: P(axis), stage_params)
    return shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(pspecs, P()),
        out_specs=P(),
    )(stage_params, microbatches)


def split_layers_to_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params -> (n_stages, L//n_stages, ...)."""
    def re(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])

    return jax.tree.map(re, stacked_params)
