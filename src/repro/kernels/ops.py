"""Public jit'd wrappers around the Pallas kernels.

Handle padding to MXU-aligned blocks, batch flattening, weight
pre-quantization (the DAC programming step), and CPU fallback:
on non-TPU backends the wrappers run the kernels in interpret mode when
``interpret=None`` (auto), so the whole framework is runnable here while
the lowered TPU path keeps the real kernels.

Wire format (DESIGN.md §9): both projection wrappers accept
``codes=True`` (requires ``adc``) to emit the edge-ADC's integer codes
directly from the fused epilogue — the int8 payload the hardware streams —
instead of dequantized float32. The matching ``(scale, zero)`` metadata is
static, from :func:`repro.core.adc.readout_scale_zero`.

Energy accounting (DESIGN.md §10): the conversion count a wrapper's
fused-ADC epilogue performs is :func:`fused_adc_conversions` — M per
REAL input row. MXU padding rows (``block_p``/``block_r`` round-up) are a
simulator artifact: their epilogue outputs are sliced off before the
wrapper returns and the modeled hardware never converts them, so they are
never priced. Adapters expose the same count via ``fn.frame_conversions``
so the frontend's event ledger and the kernel's emitted payload cannot
drift (asserted in tests/test_power.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bayer as bayer_mod
from repro.core import projection as proj_mod
from repro.core import pwm as pwm_mod
from repro.kernels import ref
from repro.kernels.bayer_sensor import bayer_frame_pallas
from repro.kernels.ip2_megakernel import (
    ip2_fused_embed_pallas,
    ip2_ragged_pallas,
)
from repro.kernels.ip2_project import IP2KernelParams, ip2_project_pallas
from repro.kernels.ip2_project_sparse import ip2_project_sparse_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.kernels.vit_delta_attention import delta_attention_pallas


def _auto_interpret(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0.0) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


class ProgrammedWeights(NamedTuple):
    """Offline DAC-programmed projection weights (satellite of DESIGN.md
    §11): the output of :func:`repro.core.pwm.quantize_weights`, computed
    once at deploy time — the hardware programs its weight DACs once, not
    per exposure. Every projection wrapper accepts this in place of raw
    float ``weights`` and skips the per-call re-quantization; the per-call
    path stays as the fallback and is bitwise-equal (the STE grid is
    deterministic)."""

    w_q: jnp.ndarray     # (M, N2) float weights ON the DAC grid
    scale: jnp.ndarray   # per-output scale (diagnostic; kernels ignore it)


def program_weights(
    weights: jnp.ndarray, spec: proj_mod.PatchSpec
) -> ProgrammedWeights:
    """Offline DAC programming entry, mirroring ``vit.prepare_quant_embed``
    for the backend's embed weights: run the weight-DAC quantization once
    and reuse the programmed array across every projection call.
    Idempotent: already-programmed weights pass through unchanged (the DAC
    grid is a fixed point of its own quantizer)."""
    if isinstance(weights, ProgrammedWeights):
        return weights
    w_q, scale = pwm_mod.quantize_weights(weights, spec.quant)
    return ProgrammedWeights(w_q=w_q, scale=scale)


def _dac_weights(weights, spec: proj_mod.PatchSpec) -> jnp.ndarray:
    """Resolve raw-or-programmed weights to the DAC-grid array."""
    if isinstance(weights, ProgrammedWeights):
        return weights.w_q
    w_q, _ = pwm_mod.quantize_weights(weights, spec.quant)  # DAC programming
    return w_q


def fused_adc_conversions(n_rows, spec: proj_mod.PatchSpec, adc=None):
    """ADC conversions one projection call performs for ``n_rows`` real
    patch rows: M per row when a fused ADC epilogue runs (``adc`` given),
    0 otherwise (the caller's own readout converts, and must count).
    ``n_rows`` may be a traced array — the count is data, not shape.
    Padding rows never count (see module docstring)."""
    if adc is None:
        return 0 * n_rows
    return n_rows * spec.n_vectors


def fused_sign_comparisons(n_rows, spec: proj_mod.PatchSpec):
    """Comparator firings of one sign-readout projection call: one per
    (real row, vector) — the ADC-less counterpart of
    :func:`fused_adc_conversions` (priced as ``sign_comparisons``, not
    ``adc_conversions``, DESIGN.md §13)."""
    return n_rows * spec.n_vectors


def kernel_params_from_spec(
    spec: proj_mod.PatchSpec, adc=None, codes: bool = False,
    readout: str = "adc",
) -> IP2KernelParams:
    if codes and adc is None:
        raise ValueError("codes=True requires an ADCSpec (the codes ARE the ADC output)")
    if readout == "sign" and codes:
        raise ValueError(
            "readout='sign' emits the 1-bit sign wire; the int code wire "
            "(codes=True) only exists on the ADC readout"
        )
    return IP2KernelParams(
        readout=readout,
        n2=spec.pixels_per_patch,
        pwm_levels=spec.quant.pwm_levels,
        droop=spec.summer.droop_factor(),
        v_ref=spec.summer.v_ref,
        nl_kind=spec.nl.kind if spec.nl.kind in ("relu",) else "none",
        v_sat=spec.nl.v_sat,
        adc_bits=adc.bits if adc is not None else 8,
        adc_vmin=adc.v_min if adc is not None else -1.0,
        adc_vmax=adc.v_max if adc is not None else 1.0,
        adc_enable=adc is not None,
        adc_out_codes=codes,
    )


def bayer_frame(
    rgb: jnp.ndarray, cutoff_nyquist: float, interpret: bool | None = None
) -> jnp.ndarray:
    """The Bayer sensor's optics and mosaic in one kernel pass:
    ``(..., H, W, 3)`` RGB -> ``(..., H, W)`` float32 raw frame, equal to
    ``bayer.mosaic(bayer.antialias(rgb, cutoff_nyquist,
    channels_last=True))`` (:mod:`repro.kernels.bayer_sensor`)."""
    lead, (h, w, c) = rgb.shape[:-3], rgb.shape[-3:]
    flat = rgb.astype(jnp.float32).reshape((-1, h, w, c))
    out = bayer_frame_pallas(flat, bayer_mod.aa_taps(cutoff_nyquist),
                             bayer_mod.RGGB,
                             interpret=_auto_interpret(interpret))
    return out.reshape(lead + (h, w))


def ip2_project(
    patches: jnp.ndarray,          # (..., P, N2) in [0,1]
    weights: jnp.ndarray,          # (M, N2) float (pre-DAC)
    spec: proj_mod.PatchSpec,
    adc=None,
    bias: jnp.ndarray | None = None,
    codes: bool = False,
    readout: str = "adc",
    block_p: int = 128,
    block_m: int = 128,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Kernel-backed equivalent of core.projection.analog_project_patches
    (+ fused ADC readout when ``adc`` is given). Returns (..., P, M) —
    float32 readout, or the int code payload when ``codes=True`` (the bias
    then lives in the ``zero`` metadata, not the payload), or the bool
    sign wire when ``readout="sign"`` (DESIGN.md §13; metadata from
    :func:`repro.core.adc.sign_scale_zero`)."""
    w_q = _dac_weights(weights, spec)
    m, n2 = w_q.shape
    lead = patches.shape[:-1]
    flat = patches.reshape(-1, n2)
    # small row batches (the compact path's k rows, or the temporal gate's
    # j-stale rows — DESIGN.md §6) would otherwise pad up to a full
    # 128-row MXU tile; clamp to the sublane-aligned row count instead.
    block_p = max(8, min(block_p, -(-flat.shape[0] // 8) * 8))

    w_t = w_q.T                                             # (N2, M)
    b = jnp.zeros((m,), jnp.float32) if bias is None else bias.astype(jnp.float32)

    p_pad = _pad_to(flat.astype(jnp.float32), 0, block_p)
    k_in = _pad_to(p_pad, 1, block_k)
    w_pad = _pad_to(_pad_to(w_t.astype(jnp.float32), 0, block_k), 1, block_m)
    b_pad = _pad_to(b, 0, block_m)[None, :]

    params = kernel_params_from_spec(spec, adc, codes, readout)
    out = ip2_project_pallas(
        k_in, w_pad, b_pad, params,
        block_p=block_p, block_m=block_m, block_k=block_k,
        interpret=_auto_interpret(interpret),
    )
    out = out[: flat.shape[0], :m]
    if readout == "sign":
        out = out.astype(bool)     # kernels emit int8 {0,1}; the wire is 1-bit
    return out.reshape(*lead, m)


def _identity_indices(patches: jnp.ndarray) -> jnp.ndarray:
    """(..., j, N2) gathered patches -> (..., j) identity row indices, the
    ragged adapter path's selection (rows are already in slot order)."""
    j = patches.shape[-2]
    return jnp.broadcast_to(
        jnp.arange(j, dtype=jnp.int32), patches.shape[:-2] + (j,)
    )


def ip2_project_fn(spec: proj_mod.PatchSpec, programmed=None, **kw):
    """Adapter matching core.frontend.ProjectFn (no fused ADC: the frontend
    applies its own readout; used to drop the kernel into apply_frontend).
    Works on both frontend modes — in compact mode the frontend hands it
    the already-gathered (..., k, N2) active patches.

    ``programmed``: optional :class:`ProgrammedWeights` to use instead of
    DAC-quantizing the passed weights on every call (offline programming).

    Ragged k (DESIGN.md §11): the frontend passes ``row_counts`` when it
    knows how many leading rows per slot are real; the adapter then routes
    through the ragged megakernel so shed rows cost zero FLOPs/bytes.
    Rows at positions >= their slot's count come back ZERO."""

    def fn(patches, weights, _spec, row_counts=None):
        w = programmed if programmed is not None else weights
        if row_counts is None:
            return ip2_project(patches, w, _spec, adc=None, **kw)
        return ip2_project_sparse(
            patches, w, _identity_indices(patches), _spec, adc=None,
            row_counts=row_counts, **kw)

    fn.supports_row_counts = True
    # no fused ADC: conversions happen in the caller's readout, not here
    fn.frame_conversions = lambda n_rows: fused_adc_conversions(n_rows, spec)
    return fn


def ip2_codes_fn(spec: proj_mod.PatchSpec, adc, programmed=None, **kw):
    """Adapter matching core.frontend.ProjectFn whose output is the wire
    format: int codes straight from the kernel's fused ADC epilogue
    (DESIGN.md §9). The frontend detects ``emits_codes`` and skips its own
    jnp re-quantization — the conversion happens exactly once, at the
    array edge, inside the kernel. ``programmed``/``row_counts`` as in
    :func:`ip2_project_fn` (shed rows are ZERO codes; the ledger's
    ``frame_conversions`` is priced on real rows by the caller)."""

    def fn(patches, weights, _spec, row_counts=None):
        w = programmed if programmed is not None else weights
        if row_counts is None:
            return ip2_project(patches, w, _spec, adc=adc, codes=True, **kw)
        return ip2_project_sparse(
            patches, w, _identity_indices(patches), _spec, adc=adc,
            codes=True, row_counts=row_counts, **kw)

    fn.supports_row_counts = True
    fn.emits_codes = True
    # the fused epilogue converts every real row's M outputs exactly once
    fn.frame_conversions = lambda n_rows: fused_adc_conversions(
        n_rows, spec, adc)
    return fn


def ip2_sign_fn(spec: proj_mod.PatchSpec, programmed=None, **kw):
    """Adapter matching core.frontend.ProjectFn whose output is the 1-bit
    sign wire (DESIGN.md §13): bool comparator bits straight from the
    kernel's ADC-less epilogue. The frontend detects ``emits_sign`` and
    attaches :func:`repro.core.adc.sign_scale_zero` metadata instead of the
    ADC affine. ``programmed``/``row_counts`` as in :func:`ip2_project_fn`
    (shed rows come back as bit 0 with gain 0)."""

    def fn(patches, weights, _spec, row_counts=None):
        w = programmed if programmed is not None else weights
        if row_counts is None:
            return ip2_project(patches, w, _spec, readout="sign", **kw)
        return ip2_project_sparse(
            patches, w, _identity_indices(patches), _spec,
            readout="sign", row_counts=row_counts, **kw)

    fn.supports_row_counts = True
    fn.emits_sign = True
    # no ADC ramp runs: the epilogue fires one comparator per (row, vector)
    fn.frame_conversions = lambda n_rows: fused_adc_conversions(n_rows, spec)
    fn.frame_sign_comparisons = lambda n_rows: fused_sign_comparisons(
        n_rows, spec)
    return fn


def ip2_conv(
    frame: jnp.ndarray,            # (H, W) or (B, H, W) pixel voltages [0,1]
    weights: jnp.ndarray,          # (C, K²) float (pre-DAC) or ProgrammedWeights
    conv: proj_mod.ConvSpec,
    adc=None,
    bias: jnp.ndarray | None = None,
    codes: bool = False,
    readout: str = "adc",
    block_m: int = 128,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Conv-in-pixel mode (DESIGN.md §13): strided K×K in-pixel convolution
    reusing the PWM/charge-share projection fabric — the frame's windows
    are the 'patches' (``extract_windows``), the C output channels are the
    'vectors', and the whole mode-selectable epilogue (fused ADC, code
    wire, sign readout) applies per window. Returns (..., gh·gw, C) in
    row-major window order, dtype per the chosen epilogue.

    The energy difference from patch-bank projection is the weight DAC:
    conv holds ONE K²×C kernel bank, so a static kernel is programmed once
    at deploy (``dac_reprograms`` ≈ 0 per frame) while cycling kernels
    through the bank reprograms per frame — priced by
    :func:`repro.core.power.conv_frame_events`, never by this wrapper."""
    windows = proj_mod.extract_windows(frame, conv.kernel, conv.stride)
    return ip2_project(
        windows, weights, conv.patch_spec(), adc=adc, bias=bias,
        codes=codes, readout=readout, block_m=block_m, block_k=block_k,
        interpret=interpret,
    )


def _ragged_tables(
    indices: jnp.ndarray,          # (..., k) active patch indices
    n_patches: int,
    row_counts,                    # scalar/broadcastable int counts, or None
    block_r: int,
):
    """Slot-major tables for the ragged megakernel entries.

    Returns ``(table, counts, n_banks)`` where ``table`` is
    (slots * n_banks * block_r,) int32 dense row indices — slot s's k
    indices (batch offset folded in), extended to a whole number of
    ``block_r`` banks by repeating the slot's LAST index (the clamp the
    kernel's row index_maps apply anyway, so the pipeliner sees unchanged
    block indices on pad rows and elides their copies) — and ``counts`` is
    (slots,) int32 real-row counts clipped to [0, k]. Counts are DATA:
    block shapes and the table length depend only on k, so one compile
    serves every governor tier."""
    lead = indices.shape[:-1]
    k = indices.shape[-1]
    idx2 = indices.reshape(-1, k).astype(jnp.int32)
    batch = idx2.shape[0]
    offsets = jnp.arange(batch, dtype=jnp.int32) * n_patches
    flat2 = jnp.clip(idx2 + offsets[:, None], 0, batch * n_patches - 1)
    n_banks = -(-k // block_r)
    rps = n_banks * block_r
    pos = jnp.minimum(jnp.arange(rps), k - 1)
    table = flat2[:, pos].reshape(-1)
    if row_counts is None:
        counts = jnp.full((batch,), k, jnp.int32)
    else:
        counts = jnp.broadcast_to(jnp.asarray(row_counts), lead)
        counts = jnp.clip(counts.reshape(-1).astype(jnp.int32), 0, k)
    return table, counts, n_banks


def _mask_ragged_rows(out, counts, k):
    """Zero rows at positions >= their slot's count. The kernel already
    zeroes whole inactive banks; this masks the partial last active bank,
    whose tail rows hold clamped duplicates of the slot's last real row —
    making 'rows past counts are zero' exact per row."""
    mask = jnp.arange(k, dtype=jnp.int32)[None, :] < counts[:, None]
    return jnp.where(mask[..., None], out, jnp.zeros((), out.dtype))


def ip2_project_sparse(
    patches: jnp.ndarray,          # (..., P, N2) dense patch grid in [0,1]
    weights: jnp.ndarray,          # (M, N2) float (pre-DAC) or ProgrammedWeights
    indices: jnp.ndarray,          # (..., k) active patch indices
    spec: proj_mod.PatchSpec,
    adc=None,
    bias: jnp.ndarray | None = None,
    codes: bool = False,
    readout: str = "adc",
    row_counts=None,               # (...,) int real rows per slot, or None
    block_r: int | None = None,
    block_m: int = 128,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Compact-first projection: compute features for ONLY the ``indices``
    rows of the dense patch grid (+ fused ADC readout when ``adc`` is
    given; int code payload when ``codes=True``). The gather happens inside
    the kernel via scalar-prefetched index_maps (DESIGN.md §3.2), so
    deselected patches cost no FLOPs and no VMEM traffic. Returns
    (..., k, M) in the order of ``indices``.

    ``row_counts`` (DESIGN.md §11) switches to the ragged megakernel: per
    batch slot, only the leading ``row_counts`` rows of ``indices`` are
    computed — banks of ``block_r`` rows past a slot's count skip the MXU
    and their DMAs are elided, so governor-shed tokens cost zero FLOPs and
    zero VMEM traffic (not masked-but-computed work). Counts are data
    (one compile across tiers); rows at positions >= the count return
    ZERO. With ``row_counts=None`` the dense-k sparse kernel runs and
    output is bitwise-identical to the ragged path at full counts.

    ``block_r`` rows are batched per grid step (arbitrary, non-contiguous
    rows — selection stays patch-granular); ``None`` picks the
    sublane-aligned row count, mirroring ``ip2_project``'s ``block_p``
    clamp, so multi-row batches don't serialize one matmul per row.
    """
    w_q = _dac_weights(weights, spec)
    m, n2 = w_q.shape
    lead = patches.shape[:-2]
    n_patches = patches.shape[-2]
    if indices.shape[:-1] != lead:
        raise ValueError(f"indices lead {indices.shape[:-1]} != patches lead {lead}")
    k = indices.shape[-1]

    flat_p = patches.reshape(-1, n2).astype(jnp.float32)   # (B*P, N2)
    batch = flat_p.shape[0] // n_patches

    b = jnp.zeros((m,), jnp.float32) if bias is None else bias.astype(jnp.float32)
    k_in = _pad_to(flat_p, 1, block_k)
    w_pad = _pad_to(_pad_to(w_q.T.astype(jnp.float32), 0, block_k), 1, block_m)
    b_pad = _pad_to(b, 0, block_m)[None, :]
    params = kernel_params_from_spec(spec, adc, codes, readout)

    if row_counts is not None:
        # a bank stays one sublane tile even when k < 8: the chip tiles
        # the (bank, M) output block by 8 rows; pad rows are clamped
        # duplicates, masked below
        br = 8 if block_r is None else block_r
        table, counts, n_banks = _ragged_tables(indices, n_patches, row_counts, br)
        out = ip2_ragged_pallas(
            table, counts, k_in, w_pad, b_pad, params, n_banks=n_banks,
            block_r=br, block_m=block_m, block_k=block_k,
            interpret=_auto_interpret(interpret),
        )
        out = out.reshape(batch, n_banks * br, -1)[:, :k, :m]
        out = _mask_ragged_rows(out, counts, k)
        if readout == "sign":
            out = out.astype(bool)
        return out.reshape(*lead, k, m)

    # fold the batch into the row index: row_idx addresses (B*P) dense rows
    offsets = jnp.arange(batch, dtype=jnp.int32) * n_patches
    flat_idx = (indices.reshape(batch, k).astype(jnp.int32) + offsets[:, None]).reshape(-1)
    flat_idx = jnp.clip(flat_idx, 0, flat_p.shape[0] - 1)

    n_rows = flat_idx.shape[0]
    if block_r is None:
        block_r = 8                       # sublane-aligned default
    block_r = max(1, min(block_r, n_rows))
    # pad the row table to a bank multiple with clipped duplicates (their
    # output rows are computed and discarded by the slice below)
    flat_idx = _pad_to(flat_idx, 0, block_r, value=0)

    out = ip2_project_sparse_pallas(
        flat_idx, k_in, w_pad, b_pad, params,
        block_r=block_r, block_m=block_m, block_k=block_k,
        interpret=_auto_interpret(interpret),
    )
    out = out[:n_rows, :m]
    if readout == "sign":
        out = out.astype(bool)
    return out.reshape(*lead, k, m)


def ip2_fused_embed(
    patches: jnp.ndarray,          # (..., P, N2) dense patch grid in [0,1]
    weights: jnp.ndarray,          # (M, N2) float (pre-DAC) or ProgrammedWeights
    indices: jnp.ndarray,          # (..., k) active patch indices
    spec: proj_mod.PatchSpec,
    adc,                           # ADCSpec — the fused seam IS code space
    w8: jnp.ndarray,               # (M, D) int8 embed weight codes
    s_w: jnp.ndarray,              # (D,) float32 per-col embed scales
    row_counts=None,               # (...,) int real rows per slot, or None
    block_r: int = 8,
    block_m: int | None = None,    # None = roofline pick: m_steps=1 up to 512
    block_k: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused frontend megakernel (DESIGN.md §11): projection + fused ADC +
    the backend's w8a8 first-layer embed matmul in ONE kernel — the int8
    codes go straight from the epilogue's VMEM scratch into the MXU,
    never round-tripping through HBM between frontend and backend.

    Returns (..., k, D) float32 — the ``y = (codes @ w8) * lsb * s_w``
    term of ``vit._embed_tokens``'s quant-embed affine, bitwise-equal the
    staged ``ip2_project_sparse(codes=True)`` → ``quant_matmul_pre`` path
    for the same selection (asserted in tests/test_megakernel.py). The
    caller adds :func:`fused_embed_zero_term` and the per-token gain
    exactly as the staged path does. ``row_counts`` behaves as in
    :func:`ip2_project_sparse` (shed rows are zero).
    """
    if adc is None:
        raise ValueError("ip2_fused_embed requires an ADCSpec: the fused "
                         "seam only exists in ADC code space (DESIGN.md §9)")
    w_q = _dac_weights(weights, spec)
    m, n2 = w_q.shape
    if w8.shape[0] != m:
        raise ValueError(f"embed rows {w8.shape[0]} != n_vectors {m}")
    d = w8.shape[1]
    lead = patches.shape[:-2]
    n_patches = patches.shape[-2]
    if indices.shape[:-1] != lead:
        raise ValueError(f"indices lead {indices.shape[:-1]} != patches lead {lead}")
    k = indices.shape[-1]

    flat_p = patches.reshape(-1, n2).astype(jnp.float32)
    batch = flat_p.shape[0] // n_patches
    br = block_r          # one sublane tile per bank, as in the ragged path
    table, counts, n_banks = _ragged_tables(indices, n_patches, row_counts, br)

    # roofline-picked default (benchmarks/bench_roofline.py): one vector-bank
    # step per row bank (m_steps=1 up to a 512-lane block) minimizes grid
    # steps — each extra m step re-gathers every patch-row block
    if block_m is None:
        block_m = min(512, -(-m // 128) * 128)

    k_in = _pad_to(flat_p, 1, block_k)
    w_pad = _pad_to(_pad_to(w_q.T.astype(jnp.float32), 0, block_k), 1, block_m)
    # embed weight pad rows MUST be zero: projection pad columns carry junk
    # codes (epilogue of an empty accumulator) and the zero rows annihilate
    # them exactly in the int32 sum — the bitwise-parity keystone.
    w8_pad = _pad_to(_pad_to(w8, 0, block_m, value=0), 1, 128, value=0)
    sw_pad = _pad_to(s_w.astype(jnp.float32), 0, 128)[None, :]

    # per-row activation scale = the ADC's single static LSB, materialized
    # as a buffer so the kernel epilogue multiplies in quant_matmul order
    sa_rows = jnp.full((table.shape[0], 1), adc.lsb, jnp.float32)

    params = kernel_params_from_spec(spec, adc, codes=True)
    out = ip2_fused_embed_pallas(
        table, counts, k_in, w_pad, w8_pad, sw_pad, sa_rows, params,
        n_banks=n_banks, block_r=br, block_m=block_m, block_k=block_k,
        interpret=_auto_interpret(interpret),
    )
    out = out.reshape(batch, n_banks * br, -1)[:, :k, :d]
    if row_counts is not None:
        out = _mask_ragged_rows(out, counts, k)
    return out.reshape(*lead, k, d)


def fused_embed_zero_term(zero, w8: jnp.ndarray, s_w: jnp.ndarray):
    """The affine ``zero @ dequant(w8)`` term the fused kernel does NOT
    compute (it is selection-independent): identical expression to
    ``vit._embed_tokens``'s staged ``zero_term`` so fused = staged holds
    bitwise. ``zero`` broadcasts over (..., M)."""
    return zero @ (w8.astype(jnp.float32) * s_w[None, :])


def quant_matmul_pre(
    a8: jnp.ndarray,               # (..., K) int8 pre-quantized activations
    s_a: jnp.ndarray,              # (...,) float32 per-row scales
    w8: jnp.ndarray,               # (K, M) int8 codes
    s_w: jnp.ndarray,              # (M,) scales
    out_dtype=jnp.float32,
    block_p: int = 128,
    block_m: int = 128,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """y = (a8 @ w8) * s_a * s_w for ALREADY-quantized activations.

    The ADC-code consumption entry (DESIGN.md §9): edge-ADC codes are the
    activation quantization — feeding them here incurs no second rounding.
    ``s_a`` broadcasts against the row dims of ``a8`` (a scalar works for
    the ADC's single static LSB scale)."""
    k, m = w8.shape
    lead = a8.shape[:-1]
    flat = a8.reshape(-1, k)
    s_flat = jnp.broadcast_to(jnp.asarray(s_a, jnp.float32), lead).reshape(-1)

    a_pad = _pad_to(_pad_to(flat, 0, block_p), 1, block_k)
    sa_pad = _pad_to(s_flat, 0, block_p)[:, None]
    w_pad = _pad_to(_pad_to(w8, 0, block_k), 1, block_m)
    sw_pad = _pad_to(s_w.astype(jnp.float32), 0, block_m)[None, :]

    # thread the requested out_dtype into the kernel: the epilogue casts
    # from its f32 accumulator exactly once, so bf16 consumers don't pay a
    # second materialization (accumulation itself stays int32 -> f32)
    out = quant_matmul_pallas(
        a_pad, sa_pad, w_pad, sw_pad,
        block_p=block_p, block_m=block_m, block_k=block_k,
        out_dtype=out_dtype, interpret=_auto_interpret(interpret),
    )
    out = out[: flat.shape[0], :m]
    return out.reshape(*lead, m)


def quant_matmul(
    a: jnp.ndarray,                # (..., K) float activations
    w8: jnp.ndarray,               # (K, M) int8 codes
    s_w: jnp.ndarray,              # (M,) scales
    out_dtype=None,
    block_p: int = 128,
    block_m: int = 128,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """y = a @ dequant(w8): quantizes ``a`` per-row to int8 on the host
    (``ref.quantize_activations_ref``) and defers to
    :func:`quant_matmul_pre`. Activations that are already int8 codes
    (e.g. edge-ADC output) should call ``quant_matmul_pre`` directly."""
    out_dtype = out_dtype or a.dtype
    k, _ = w8.shape
    lead = a.shape[:-1]
    flat = a.reshape(-1, k)
    a8, s_a = ref.quantize_activations_ref(flat)
    out = quant_matmul_pre(
        a8, s_a, w8, s_w, out_dtype=out_dtype,
        block_p=block_p, block_m=block_m, block_k=block_k, interpret=interpret,
    )
    return out.reshape(*lead, w8.shape[1])


def quantize_weights_int8(w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(K, M) float -> int8 codes + per-col scale (offline weight prep)."""
    amax = jnp.max(jnp.abs(w), axis=0)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    w8 = jnp.clip(jnp.round(w / scale[None, :]), -127, 127).astype(jnp.int8)
    return w8, scale.astype(jnp.float32)


def delta_attention(
    attn_params: dict,
    h: jnp.ndarray,                # (B, S, d) normed layer input
    token_valid: jnp.ndarray,      # (B, S) bool key mask
    q_counts: jnp.ndarray,         # (B,) int32 stale prefix length (DATA)
    n_heads: int,
    block_q: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Ragged stale-Q attention for the delta-gated backend (DESIGN.md
    §14): Q/K/V projections in plain einsums (per-row work — XLA handles
    it), then the Pallas kernel scores ONLY the ``q_counts`` stale query
    rows per slot against the full key set, then the output projection.
    Rows past a slot's count come back zero; the delta gate keeps their
    cached values, so they never reach the residual stream."""
    del n_heads  # shape-carried by the projection weights
    q = jnp.einsum("bsd,dhk->bshk", h, attn_params["wq"]) + attn_params["bq"]
    k = jnp.einsum("bsd,dhk->bshk", h, attn_params["wk"]) + attn_params["bk"]
    v = jnp.einsum("bsd,dhk->bshk", h, attn_params["wv"]) + attn_params["bv"]
    o = delta_attention_pallas(
        q, k, v, token_valid, q_counts,
        block_q=block_q, interpret=_auto_interpret(interpret),
    )
    return jnp.einsum("bshk,hkd->bsd", o, attn_params["wo"])
