"""Pallas TPU kernel — w8a8 quantized matmul (DESIGN.md §7, §9).

The paper's derived digital optimization: the same "quantize-the-multiply"
insight applied to backend projections and KV-cache dequant-matmuls.
Weights arrive as int8 codes with a per-output-channel scale (exactly the
weight-DAC abstraction). Activations arrive ALREADY quantized — this
kernel never quantizes them itself. The two entry points in ops.py differ
only in who did that quantization:

* ``ops.quant_matmul`` — float activations; the *wrapper* quantizes them
  per-row on the host (``ref.quantize_activations_ref``) before the call.
* ``ops.quant_matmul_pre`` — pre-quantized int8 codes + scales straight
  in. This is the ADC-code consumption path (DESIGN.md §9): the edge ADC
  already performed the activation quantization at conversion time, so
  feeding its codes through here incurs no second rounding.

    y[p, m] = (sum_k a8[p,k] * w8[k,m]) * s_a[p] * s_w[m]

int32 accumulation on the MXU, fused dequant epilogue.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _qmm_kernel(a_ref, sa_ref, w_ref, sw_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 straight into the MXU, int32 accumulator: exact, so the
    # precision is pinned (a float32 matmul-precision context must not
    # reach it — the MXU takes no fp32 contract on int8 operands)
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        # sa is a (block_p, 1) column, sw a (1, block_m) row
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * sa_ref[...]
                      * sw_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_p", "block_m", "block_k", "out_dtype", "interpret")
)
def quant_matmul_pallas(
    a8: jnp.ndarray,        # (P, K) int8 activations
    s_a: jnp.ndarray,       # (P, 1) float32 per-row scales
    w8: jnp.ndarray,        # (K, M) int8 weights
    s_w: jnp.ndarray,       # (1, M) float32 per-col scales
    block_p: int = 128,
    block_m: int = 128,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    P, K = a8.shape
    K2, M = w8.shape
    assert K == K2 and s_a.shape == (P, 1) and s_w.shape == (1, M)
    if a8.dtype != jnp.int8 or w8.dtype != jnp.int8:
        raise ValueError(
            f"w8a8 kernel takes int8 operands, got {a8.dtype} x {w8.dtype}")
    assert P % block_p == 0 and M % block_m == 0 and K % block_k == 0
    k_steps = K // block_k
    grid = (P // block_p, M // block_m, k_steps)

    return pl.pallas_call(
        functools.partial(_qmm_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_p, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_p, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((block_k, block_m), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_m), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_p, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((P, M), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_p, block_m), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a8, s_a, w8, s_w)
