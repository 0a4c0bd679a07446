"""Pure-jnp oracles for every Pallas kernel (shape/dtype-exact)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import adc as adc_mod
from repro.core import bayer as bayer_mod
from repro.kernels.ip2_project import IP2KernelParams


def bayer_frame_ref(rgb: jnp.ndarray, cutoff_nyquist: float) -> jnp.ndarray:
    """Oracle for bayer_frame_pallas: the AA filter on each channel in
    place, then the RGGB mosaic."""
    return bayer_mod.mosaic(
        bayer_mod.antialias(rgb, cutoff_nyquist, channels_last=True))


def ip2_project_ref(
    patches: jnp.ndarray, w_q: jnp.ndarray, bias: jnp.ndarray, params: IP2KernelParams
) -> jnp.ndarray:
    """Oracle for ip2_project_pallas (same padded shapes), including the
    ``adc_out_codes`` wire-format output (DESIGN.md §9) and the ADC-less
    ``readout="sign"`` comparator epilogue (DESIGN.md §13, int8 {0,1} to
    match the kernel's out_dtype; the ops wrapper re-types to bool)."""
    n = params.pwm_levels - 1
    xq = jnp.round(jnp.clip(patches, 0.0, 1.0) * n) * (1.0 / n)
    acc = xq.astype(jnp.float32) @ w_q.astype(jnp.float32)
    out = acc * (params.droop / params.n2) + params.v_ref
    if params.nl_kind == "relu":
        out = jnp.clip(out, 0.0, params.v_sat)
    if params.readout == "sign":
        return adc_mod.sign_encode(out, params.v_ref).astype(jnp.int8)
    if not params.adc_enable:
        return out - (params.v_ref - bias[None, :])
    spec = params.adc_spec()
    if params.adc_out_codes:
        return adc_mod.encode(out, spec)
    return adc_mod.digital_readout(out, params.v_ref, bias[None, :], spec)


def ip2_project_sparse_ref(
    row_idx: jnp.ndarray,
    patches: jnp.ndarray,
    w_q: jnp.ndarray,
    bias: jnp.ndarray,
    params: IP2KernelParams,
) -> jnp.ndarray:
    """Oracle for ip2_project_sparse_pallas (same padded shapes, any
    block_r): an explicit row gather followed by the dense projection."""
    return ip2_project_ref(patches[row_idx], w_q, bias, params)


def ip2_conv_ref(
    frame: jnp.ndarray,
    w_q: jnp.ndarray,
    bias: jnp.ndarray,
    conv,                          # core.projection.ConvSpec (geometry only)
    params: IP2KernelParams,
) -> jnp.ndarray:
    """Oracle for ops.ip2_conv: explicit python-loop strided K×K window
    slicing (independent of the wrapper's im2col gather) followed by the
    dense projection oracle — (..., gh*gw, C) in row-major window order.
    ``w_q`` is (K², C) on the DAC grid, as in :func:`ip2_project_ref`."""
    k, s = conv.kernel, conv.stride
    frames = frame if frame.ndim == 3 else frame[None]
    b, h, w = frames.shape
    gh = (h - k) // s + 1
    gw = (w - k) // s + 1
    wins = [
        frames[:, i * s:i * s + k, j * s:j * s + k].reshape(b, k * k)
        for i in range(gh) for j in range(gw)
    ]
    windows = jnp.stack(wins, axis=1)                    # (b, gh*gw, K²)
    out = ip2_project_ref(windows.reshape(-1, k * k), w_q, bias, params)
    out = out.reshape(b, gh * gw, -1)
    return out if frame.ndim == 3 else out[0]


def ip2_fused_embed_ref(
    row_idx: jnp.ndarray,
    patches: jnp.ndarray,
    w_q: jnp.ndarray,
    w8: jnp.ndarray,
    s_w: jnp.ndarray,
    params: IP2KernelParams,
) -> jnp.ndarray:
    """Oracle for ip2_fused_embed_pallas (same padded shapes): the staged
    composition — sparse projection to ADC codes, then the w8a8 embed
    matmul with the ADC LSB as the (single, static) activation scale."""
    bias = jnp.zeros((w_q.shape[1],), jnp.float32)
    codes = ip2_project_sparse_ref(row_idx, patches, w_q, bias, params)
    lsb = jnp.full((codes.shape[0],), params.adc_spec().lsb, jnp.float32)
    return quant_matmul_ref(codes, lsb, w8, s_w, jnp.float32)


def quant_matmul_ref(
    a8: jnp.ndarray, s_a: jnp.ndarray, w8: jnp.ndarray, s_w: jnp.ndarray, out_dtype=jnp.float32
) -> jnp.ndarray:
    acc = a8.astype(jnp.int32) @ w8.astype(jnp.int32)
    return (acc.astype(jnp.float32) * s_a[:, None] * s_w[None, :]).astype(out_dtype)


def quantize_activations_ref(a: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row symmetric int8 activation quantization (the 'PWM' side)."""
    amax = jnp.max(jnp.abs(a), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    a8 = jnp.clip(jnp.round(a / scale[..., None]), -127, 127).astype(jnp.int8)
    return a8, scale.astype(jnp.float32)
