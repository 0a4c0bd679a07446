"""Pallas TPU kernel — sparse (active-patch-only) IP2 projection.

The compact-first dataflow (DESIGN.md §3): the saccade selector produces
the indices of the k active patches, and this kernel projects *only* those
rows of the dense patch array. The gather is not a separate XLA pass —
it happens in the kernel's index_maps: the active-patch row indices are
scalar-prefetched (``pltpu.PrefetchScalarGridSpec``), so before each grid
step the DMA engine fetches exactly the patch rows the step needs, straight
from the dense (P, K) array in HBM into VMEM. FLOPs and VMEM traffic both
scale with ``k / P`` (the active fraction); deselected patches are never
touched — the digital twin of "deselected patches drain their photodiodes
and power down".

Grid = (active row banks, vector banks, K banks). One grid step processes
``block_r`` *arbitrary* (non-contiguous) dense rows: the patch operand is
viewed ``(rows, 1, K)`` and passed ``block_r`` times with single-row
BlockSpecs (squeezed row dim over a ``(1, block_k)`` tile, the shape the
chip's tiling accepts) whose index_maps each read their own slot of the
prefetched row table (``idx[i*block_r + r]``),
and the kernel body stacks the gathered rows into one (block_r, block_k)
tile for the MXU. Selection therefore stays patch-granular for any saccade
pattern while the matmul and the grid amortize over a sublane-aligned row
bank — multi-row stale batches (the temporal gate's j rows, DESIGN.md §6)
no longer serialize one 1×K×M matmul per row. The full PWM / charge-share /
droop / 2T / edge-ADC epilogue stays fused exactly as in the dense kernel
(shared helpers), including the ``adc_out_codes`` wire format (int8 codes
out, DESIGN.md §9).

The wrapper in ops.py pads the row table to a multiple of ``block_r``
(clipped duplicate rows, sliced off after the call) and defaults
``block_r`` to the sublane-aligned row count, mirroring how
``ops.ip2_project`` clamps ``block_p``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ip2_project import (
    IP2KernelParams,
    analog_epilogue_tile,
    pwm_quantize_tile,
)


def _ip2_sparse_kernel(
    idx_ref, *refs, p: IP2KernelParams, k_steps: int, block_r: int
):
    """Grid = (row banks, vector banks, K banks); K innermost/arbitrary.

    ``idx_ref`` is the scalar-prefetched row table; it already steered the
    per-row BlockSpec index_maps, so ``refs[:block_r]`` hold the gathered
    rows of this bank."""
    del idx_ref  # consumed by the index_maps, not the body
    x_refs = refs[:block_r]
    w_ref, b_ref, o_ref, acc_ref = refs[block_r:]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = jnp.concatenate([r[...] for r in x_refs], axis=0)   # (block_r, block_k)
    xq = pwm_quantize_tile(x, p)
    acc_ref[...] += jnp.dot(xq, w_ref[...], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        o_ref[...] = analog_epilogue_tile(acc_ref[...], b_ref[...], p).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("params", "block_r", "block_m", "block_k", "interpret"),
)
def ip2_project_sparse_pallas(
    row_idx: jnp.ndarray,      # (R,) int32 dense row indices of active patches
    patches: jnp.ndarray,      # (P_rows, K) dense pixel voltages in [0,1]
    w_q: jnp.ndarray,          # (K, M) DAC-quantized weights (pre-quantized)
    bias: jnp.ndarray,         # (1, M)
    params: IP2KernelParams,
    block_r: int = 8,
    block_m: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Padded-shape kernel entry; use repro.kernels.ops.ip2_project_sparse.

    Returns (R, M): output row i holds the projection of dense patch row
    ``row_idx[i]`` (rows within a bank may come from anywhere in the dense
    array). ``R`` must be a multiple of ``block_r``.
    """
    p_rows, K = patches.shape
    K2, M = w_q.shape
    (R,) = row_idx.shape
    assert K == K2 and bias.shape == (1, M)
    assert R % block_r == 0 and M % block_m == 0 and K % block_k == 0, (
        f"pad shapes to blocks: {(R, K, M)} vs {(block_r, block_k, block_m)}"
    )
    k_steps = K // block_k
    grid = (R // block_r, M // block_m, k_steps)

    def _row_map(r):
        # the gather: slot r of row bank i loads dense row idx[i*block_r + r]
        return lambda i, j, k, idx: (idx[i * block_r + r], 0, k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # patches are viewed (rows, 1, K): a squeezed row dim over a
            # (1, block_k) tile, so each operand DMAs one dense row
            *(pl.BlockSpec((None, 1, block_k), _row_map(r)) for r in range(block_r)),
            pl.BlockSpec((block_k, block_m), lambda i, j, k, idx: (k, j)),
            pl.BlockSpec((1, block_m), lambda i, j, k, idx: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_r, block_m), lambda i, j, k, idx: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_r, block_m), jnp.float32)],
    )

    return pl.pallas_call(
        functools.partial(
            _ip2_sparse_kernel, p=params, k_steps=k_steps, block_r=block_r
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, M), params.out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(row_idx.astype(jnp.int32), *([patches[:, None, :]] * block_r), w_q, bias)
