"""Pallas TPU megakernel — fused frontend with ragged per-slot k (DESIGN.md §11).

Two entries share one slot-major ragged banking scheme:

* :func:`ip2_ragged_pallas` — the sparse projection of
  ``ip2_project_sparse_pallas`` re-gridded with an explicit SLOT axis and a
  scalar-prefetched per-slot ROW-COUNT table. Grid = (slots, row banks per
  slot, vector banks, K banks); a row bank is *active* iff its first row
  index is below its slot's count. Inactive banks skip the MXU entirely
  (``pl.when``) and their patch/weight index_maps collapse onto the
  previous block index, so Pallas' pipeliner elides the DMA copies — shed
  rows cost zero FLOPs and zero VMEM traffic, not masked-but-computed
  work. Raggedness is therefore quantized to ``block_r`` (one sublane-
  aligned bank), and the counts are DATA: one compile serves every
  per-slot count the governor's ``k_eff`` tiers can produce.

* :func:`ip2_fused_embed_pallas` — the full frontend seam in one kernel:
  scalar-prefetched gather of the active patch rows, PWM / charge-share
  projection, fused edge-ADC epilogue, and the w8a8 first-layer embed
  matmul of the backend — ``(codes @ W8) * lsb * s_w`` — consuming the
  int8 codes straight out of a VMEM scratch. The codes never round-trip
  through HBM between the frontend and the backend's first matmul
  (DESIGN.md §9's one-dequant-site contract holds: the epilogue here IS
  that site, bit-for-bit the arithmetic of ``quant_matmul_pallas``).

Bitwise contract (asserted in tests/test_megakernel.py): for the same
selection, the fused output equals the staged
``ip2_project_sparse(codes=True)`` → ``quant_matmul_pre`` path exactly —
same ``adc._code_grid`` epilogue, same int32 accumulation, same
``acc_f32 * s_a * s_w`` multiply order. Rows at positions >= their slot's
count are zero (the ops wrappers additionally mask the partial bank's
clamped-duplicate rows, so the contract is exact per row).
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


def _bank_active(i, s, cnt_ref, block_r):
    """A row bank computes iff its first row position is a real row of its
    slot — the ragged-k predicate shared by the kernel bodies."""
    return (i * block_r) < cnt_ref[s]


def _row_map(r, rows_per_slot, block_r):
    """Gather index_map for row slot ``r`` of a bank: clamp the position
    into the slot's VALID prefix (``min(pos, cnt-1)``) so every row of an
    inactive bank maps to the same dense row as the slot's last real row —
    consecutive inactive grid steps then present an unchanged block index
    and the pipeliner elides their copies (zero VMEM traffic)."""

    def m(s, i, j, k, idx, cnt):
        lim = jnp.maximum(jnp.minimum(cnt[s], rows_per_slot) - 1, 0)
        pos = jnp.minimum(i * block_r + r, lim)
        return (idx[s * rows_per_slot + pos], 0, k)

    return m


def _w_map(block_r):
    """Weight index_map: inactive banks pin the block to (0, 0) so their
    steps stream no weight bytes either (same elision mechanism)."""

    def m(s, i, j, k, idx, cnt):
        act = (i * block_r) < cnt[s]
        return (jnp.where(act, k, 0), jnp.where(act, j, 0))

    return m


# ---------------------------------------------------------------------------
# ragged sparse projection
# ---------------------------------------------------------------------------

def _ragged_kernel(
    idx_ref, cnt_ref, *refs, p: IP2KernelParams, k_steps: int, block_r: int
):
    """Grid = (slots, row banks, vector banks, K banks); K innermost."""
    del idx_ref  # consumed by the index_maps, not the body
    x_refs = refs[:block_r]
    w_ref, b_ref, o_ref, acc_ref = refs[block_r:]
    s, i = pl.program_id(0), pl.program_id(1)
    act = _bank_active(i, s, cnt_ref, block_r)

    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(act)
    def _mac():
        x = jnp.concatenate([r[...] for r in x_refs], axis=0)
        acc_ref[...] += jnp.dot(
            pwm_quantize_tile(x, p), w_ref[...],
            preferred_element_type=jnp.float32,
        )

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _epilogue():
        out = analog_epilogue_tile(acc_ref[...], b_ref[...], p)
        # inactive banks write zeros: shed rows are defined, never garbage
        o_ref[...] = jnp.where(act, out, 0.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("params", "n_banks", "block_r", "block_m", "block_k",
                     "interpret"),
)
def ip2_ragged_pallas(
    row_idx: jnp.ndarray,     # (S * n_banks * block_r,) int32 dense row table
    row_counts: jnp.ndarray,  # (S,) int32 — real rows per slot (DATA)
    patches: jnp.ndarray,     # (P_rows, K) dense pixel voltages in [0,1]
    w_q: jnp.ndarray,         # (K, M) DAC-quantized weights
    bias: jnp.ndarray,        # (1, M)
    params: IP2KernelParams,
    n_banks: int,
    block_r: int = 8,
    block_m: int = 128,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Padded-shape entry; use ``ops.ip2_project_sparse(row_counts=...)``.

    Returns (S * n_banks * block_r, M): slot s owns rows
    ``[s * n_banks * block_r, (s+1) * n_banks * block_r)``; within a slot,
    row r holds the projection of dense row ``row_idx[s * rps + r]`` when
    ``r`` falls in an active bank, else zeros.
    """
    p_rows, K = patches.shape
    K2, M = w_q.shape
    (R,) = row_idx.shape
    (S,) = row_counts.shape
    rps = n_banks * block_r
    assert K == K2 and bias.shape == (1, M) and R == S * rps
    assert M % block_m == 0 and K % block_k == 0, (
        f"pad shapes to blocks: {(K, M)} vs {(block_k, block_m)}"
    )
    k_steps = K // block_k
    grid = (S, n_banks, M // block_m, k_steps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            *(pl.BlockSpec((None, 1, block_k), _row_map(r, rps, block_r))
              for r in range(block_r)),
            pl.BlockSpec((block_k, block_m), _w_map(block_r)),
            pl.BlockSpec((1, block_m), lambda s, i, j, k, idx, cnt: (0, j)),
        ],
        out_specs=pl.BlockSpec(
            (block_r, block_m),
            lambda s, i, j, k, idx, cnt: (s * n_banks + i, j),
        ),
        scratch_shapes=[pltpu.VMEM((block_r, block_m), jnp.float32)],
    )

    return pl.pallas_call(
        functools.partial(
            _ragged_kernel, p=params, k_steps=k_steps, block_r=block_r
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, M), params.out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")
        ),
        interpret=interpret,
    )(row_idx.astype(jnp.int32), row_counts.astype(jnp.int32),
      *([patches[:, None, :]] * block_r), w_q, bias)


# ---------------------------------------------------------------------------
# fused projection + ADC + w8a8 embed
# ---------------------------------------------------------------------------

def _fused_kernel(
    idx_ref, cnt_ref, *refs,
    p: IP2KernelParams, k_steps: int, m_steps: int, block_r: int,
    block_m: int,
):
    """Projection accumulates per (bank, vector bank); the fused ADC
    epilogue lands each vector bank's codes in a per-bank VMEM codes
    scratch; the final (vector, K) step feeds the whole code row bank to
    the embed matmul — int32 accumulate then ``acc_f32 * lsb * s_w``,
    bit-for-bit the ``quant_matmul_pallas`` epilogue."""
    del idx_ref
    x_refs = refs[:block_r]
    w_ref, we_ref, swe_ref, sae_ref, o_ref, acc_ref, codes_ref = refs[block_r:]
    s, i = pl.program_id(0), pl.program_id(1)
    j, kk = pl.program_id(2), pl.program_id(3)
    act = _bank_active(i, s, cnt_ref, block_r)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(act)
    def _mac():
        x = jnp.concatenate([r[...] for r in x_refs], axis=0)
        acc_ref[...] += jnp.dot(
            pwm_quantize_tile(x, p), w_ref[...],
            preferred_element_type=jnp.float32,
        )

    @pl.when(kk == k_steps - 1)
    def _codes():
        # fused edge ADC: centered integer code values (f32 grid, exact)
        code = analog_epilogue_tile(acc_ref[...], 0.0, p)
        codes_ref[:, pl.ds(j * block_m, block_m)] = jnp.where(act, code, 0.0)

    @pl.when((j == m_steps - 1) & (kk == k_steps - 1))
    def _embed():
        @pl.when(act)
        def _active():
            # int8 x int8 on the MXU with an int32 accumulator, as in
            # quant_matmul's _qmm_kernel
            c8 = codes_ref[...].astype(jnp.int32).astype(jnp.int8)
            acc = jax.lax.dot_general(
                c8, we_ref[...], (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.int32,
            )
            # per-row activation scale (the ADC LSB) loaded from memory,
            # NOT baked as a constant: keeps the multiply association
            # identical to quant_matmul's _qmm_kernel (bitwise parity)
            o_ref[...] = (acc.astype(jnp.float32) * sae_ref[...]
                          * swe_ref[...]).astype(o_ref.dtype)

        @pl.when(jnp.logical_not(act))
        def _inactive():
            o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(
    jax.jit,
    static_argnames=("params", "n_banks", "block_r", "block_m", "block_k",
                     "interpret"),
)
def ip2_fused_embed_pallas(
    row_idx: jnp.ndarray,     # (S * n_banks * block_r,) int32 dense row table
    row_counts: jnp.ndarray,  # (S,) int32 — real rows per slot (DATA)
    patches: jnp.ndarray,     # (P_rows, K) dense pixel voltages in [0,1]
    w_q: jnp.ndarray,         # (K, M) DAC-quantized projection weights
    w8_embed: jnp.ndarray,    # (M, D) int8 embed codes (pad rows ZERO)
    sw_embed: jnp.ndarray,    # (1, D) float32 per-col embed scales
    sa_rows: jnp.ndarray,     # (R, 1) float32 per-row code scales (the ADC LSB)
    params: IP2KernelParams,
    n_banks: int,
    block_r: int = 8,
    block_m: int = 128,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Padded-shape entry; use ``ops.ip2_fused_embed``.

    Returns (S * n_banks * block_r, D) float32:
    ``(ADC_codes(project(patches[row_idx])) @ w8_embed) * lsb * sw_embed``
    — the ``y`` term of the backend's quant-embed affine (the caller adds
    the ``zero @ dequant(W8)`` term and the per-token gain, exactly as
    ``models.vit._embed_tokens`` does on the staged path). Requires
    ``params.adc_out_codes`` (the fused seam only exists in code space).
    Padding rows of ``w8_embed`` (beyond the real M) MUST be zero: the
    codes of padded projection columns are junk (the epilogue of an empty
    accumulator), and the zero rows annihilate them in the int32 sum.
    """
    if not (params.readout == "adc" and params.adc_enable
            and params.adc_out_codes):
        raise ValueError(
            "ip2_fused_embed_pallas consumes its own fused-ADC codes; "
            "params must have readout='adc', adc_enable=True and "
            "adc_out_codes=True (the sign wire has no w8a8 embed seam)"
        )
    p_rows, K = patches.shape
    K2, M = w_q.shape
    M2, D = w8_embed.shape
    (R,) = row_idx.shape
    (S,) = row_counts.shape
    rps = n_banks * block_r
    assert K == K2 and M == M2 and sw_embed.shape == (1, D) and R == S * rps
    assert sa_rows.shape == (R, 1)
    assert M % block_m == 0 and K % block_k == 0 and D % 128 == 0, (
        f"pad shapes to blocks: {(K, M, D)} vs {(block_k, block_m, 128)}"
    )
    k_steps = K // block_k
    m_steps = M // block_m
    grid = (S, n_banks, m_steps, k_steps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            *(pl.BlockSpec((None, 1, block_k), _row_map(r, rps, block_r))
              for r in range(block_r)),
            pl.BlockSpec((block_k, block_m), _w_map(block_r)),
            # embed weights/scales: one constant block, fetched once
            pl.BlockSpec((M, D), lambda s, i, j, k, idx, cnt: (0, 0)),
            pl.BlockSpec((1, D), lambda s, i, j, k, idx, cnt: (0, 0)),
            pl.BlockSpec(
                (block_r, 1), lambda s, i, j, k, idx, cnt: (s * n_banks + i, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (block_r, D), lambda s, i, j, k, idx, cnt: (s * n_banks + i, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_r, block_m), jnp.float32),   # projection acc
            pltpu.VMEM((block_r, M), jnp.float32),         # code row bank
        ],
    )

    return pl.pallas_call(
        functools.partial(
            _fused_kernel, p=params, k_steps=k_steps, m_steps=m_steps,
            block_r=block_r, block_m=block_m,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")
        ),
        interpret=interpret,
    )(row_idx.astype(jnp.int32), row_counts.astype(jnp.int32),
      *([patches[:, None, :]] * block_r), w_q, w8_embed, sw_embed,
      sa_rows.astype(jnp.float32))
