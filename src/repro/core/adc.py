"""Edge ADC model (paper §2.1) and the digital wire format (DESIGN.md §9).

Only the outputs of the selected salient patches (<25 %) are converted; the
ADC is at the array edge, one (or a few) per column group. What crosses the
imager boundary is the ADC *code* — an ``ADCSpec.bits``-wide integer — not
a float: the paper's 10x bandwidth / <30 mW/MP claims are claims about code
width. This module therefore defines two views of the same conversion:

* **Codes** (:func:`digital_codes`) — the canonical wire format: signed
  integer codes (int8 for bits <= 8) plus static ``(scale, zero)`` affine
  metadata derived from the :class:`ADCSpec` and the digital ``V_R - b``
  subtraction. ``dequantize(codes, scale, zero)`` recovers the readout.
* **Floats** (:func:`digital_readout`) — the training/simulation view,
  *defined as* ``dequantize(digital_codes(...))`` plus an STE residual, so
  the float path is bit-identical to dequantized codes by construction.

The digital side subtracts ``V_R - b`` to recover the signed projection
plus the learned bias b:

    digital_v = ADC(Out_v) - (V_R - b) = Σ(W·P)/N² + b   (up to quantization)

which in code space is the affine map ``digital_v = code * scale + zero``
with ``scale = lsb`` and ``zero = v_min + (levels//2)*lsb - V_R + b``.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ADCSpec:
    bits: int = 8
    v_min: float = -1.0
    v_max: float = 1.0
    ste: bool = True

    @property
    def levels(self) -> int:
        return 2 ** self.bits

    @property
    def lsb(self) -> float:
        return (self.v_max - self.v_min) / (self.levels - 1)

    @property
    def code_dtype(self):
        """Smallest signed integer dtype that holds the (centered) codes."""
        if self.bits <= 8:
            return jnp.int8
        if self.bits <= 16:
            return jnp.int16
        return jnp.int32


class ADCCodes(NamedTuple):
    """One frame's conversions in wire format: integer codes plus the
    static affine metadata that dequantizes them. ``codes`` is the only
    O(k·M) payload; ``scale`` is a scalar and ``zero`` broadcasts with the
    per-vector bias, so the wire stays at code width."""

    codes: jnp.ndarray   # (..., M) signed integer codes (code_dtype)
    scale: jnp.ndarray   # () float32 — volts per LSB
    zero: jnp.ndarray    # (M,) or () float32 — v_min + half·lsb - (V_R - b)


def _code_grid(v: jnp.ndarray, spec: ADCSpec) -> jnp.ndarray:
    """Centered code values as float32 (shared by the jnp path and the
    Pallas kernel epilogues so the two quantize bit-identically)."""
    half = spec.levels // 2
    clipped = jnp.clip(v, spec.v_min, spec.v_max)
    return jnp.round((clipped - spec.v_min) / spec.lsb) - half


def encode(v: jnp.ndarray, spec: ADCSpec = ADCSpec()) -> jnp.ndarray:
    """Voltage -> signed integer code (no gradients: codes are integers;
    the STE lives in :func:`digital_readout`'s float view)."""
    return _code_grid(v, spec).astype(spec.code_dtype)


def readout_scale_zero(
    v_ref: float, bias: jnp.ndarray | float = 0.0, spec: ADCSpec = ADCSpec()
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The (scale, zero) metadata of :func:`digital_codes` for a given
    reference/bias — static per (ADCSpec, V_R, b); recomputable anywhere
    without touching the payload."""
    half = spec.levels // 2
    scale = jnp.float32(spec.lsb)
    zero = jnp.float32(spec.v_min + half * spec.lsb - v_ref) + jnp.asarray(
        bias, jnp.float32
    )
    return scale, zero


def dequantize(
    codes: jnp.ndarray, scale: jnp.ndarray, zero: jnp.ndarray,
    code_bits: int | None = None,
) -> jnp.ndarray:
    """codes -> float readout: the ONE affine that is allowed to leave code
    space (DESIGN.md §9 permits it only at the backend's first matmul).

    The result does not depend on FMA contraction. XLA:CPU fuses a
    multiply that feeds an add into one FMA when both land in one fused
    loop, and which loops fuse depends on the rest of the program, so a
    plain ``codes * scale + zero`` dequantizes the same codes one rounding
    apart in two programs. Here the scale is split into parts narrow
    enough that every ``codes * part`` is exact; an FMA over exact
    products rounds as the separate add does. For codes of up to 8 bits
    the sum of the two parts is ``codes * scale`` rounded once, the value
    the plain affine gives without contraction. ``code_bits`` bounds the
    codes' width. It defaults to the width of their dtype, so float codes
    take the plain affine unless it is given."""
    c = codes.astype(jnp.float32)
    if code_bits is None:
        code_bits = 8 * jnp.dtype(codes.dtype).itemsize
    if code_bits >= 24:
        return c * scale + zero
    parts = _scale_parts(scale, 24 - code_bits)
    prod = c * parts[0]
    for part in parts[1:]:
        prod = prod + c * part
    return prod + zero


def _scale_parts(scale, part_bits: int) -> list:
    """Split a float32 ``scale`` into parts of at most ``part_bits``
    significant bits that sum to it exactly (largest first). A concrete
    scale, the usual case since it derives from the static ADCSpec, is
    split on the host, so the parts enter programs and kernels as
    constants; a traced one is split with bit masks."""
    n_parts = -(-24 // part_bits)
    clear = np.uint32(0xFFFFFFFF ^ ((1 << (24 - part_bits)) - 1))
    if isinstance(scale, jax.core.Tracer):
        rest = jnp.asarray(scale, jnp.float32)
        as_f32 = lambda u: jax.lax.bitcast_convert_type(u, jnp.float32)
        as_u32 = lambda f: jax.lax.bitcast_convert_type(f, jnp.uint32)
    else:
        rest = np.asarray(scale, np.float32)
        as_f32 = lambda u: u.view(np.float32)
        as_u32 = lambda f: f.view(np.uint32)
    parts = []
    for _ in range(n_parts - 1):
        top = as_f32(as_u32(rest) & clear)
        parts.append(top)
        rest = rest - top
    parts.append(rest)
    return parts


def digital_codes(
    out_v: jnp.ndarray,
    v_ref: float,
    bias: jnp.ndarray | float = 0.0,
    spec: ADCSpec = ADCSpec(),
) -> ADCCodes:
    """ADC conversion in wire format: codes + (scale, zero) such that
    ``dequantize(codes, scale, zero) == digital_readout(out_v, ...)``
    exactly (the float readout is defined as this dequant)."""
    scale, zero = readout_scale_zero(v_ref, bias, spec)
    return ADCCodes(encode(out_v, spec), scale, zero)


# ---------------------------------------------------------------------------
# ADC-less sign readout (DESIGN.md §13)
# ---------------------------------------------------------------------------
# A single comparator against V_R replaces the full conversion: the wire
# carries one BIT per vector (bool payload), and the readout is recovered
# through the SAME dequantize affine as the code wire — scale = 2·v_mag,
# zero = b - v_mag maps {0, 1} onto {-v_mag, +v_mag} + b, so the one
# dequant site (models.vit._embed_tokens) needs no new arithmetic.

#: representative reconstruction magnitude of a sign-only readout — matches
#: the event meter's mean-signal calibration (EnergyConstants.mean_signal_v)
SIGN_V_MAG = 0.1


def sign_scale_zero(
    bias: jnp.ndarray | float = 0.0, v_mag: float = SIGN_V_MAG
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(scale, zero) metadata of the sign wire: ``dequantize(bit, scale,
    zero) = ±v_mag + bias`` for bit in {0, 1}. Static per (bias, v_mag),
    recomputable anywhere — same contract as :func:`readout_scale_zero`."""
    scale = jnp.float32(2.0 * v_mag)
    zero = jnp.asarray(bias, jnp.float32) - jnp.float32(v_mag)
    return scale, zero


def sign_encode(out_v: jnp.ndarray, v_ref: float) -> jnp.ndarray:
    """The comparator: one bit per vector, ``out_v >= V_R``. No ramp, no
    SAR steps — the near-zero-energy readout the governor's ADC-less tier
    prices as ``sign_comparisons`` instead of ``adc_conversions``."""
    return out_v >= v_ref


def sign_code_points(
    v_ref: float, spec: ADCSpec = ADCSpec(), v_mag: float = SIGN_V_MAG
) -> tuple[int, int, int]:
    """The sign degradation expressed ON the int8 code grid — the engine's
    data-only ADC-less tier (DESIGN.md §13) maps an already-converted code
    wire onto two reconstruction points without changing dtype or shape:

        c' = c_pos if c >= c_thresh else c_neg

    ``c_thresh`` is the code of the comparator boundary ``out_v == V_R``;
    ``c_pos``/``c_neg`` dequantize (through the wire's own ``(scale,
    zero)``) to ±v_mag + bias. All three are bias-independent ints, static
    per (ADCSpec, V_R, v_mag) — pure data for a compiled engine step."""
    half = spec.levels // 2
    lo, hi = -half, spec.levels - 1 - half
    v_r = min(max(v_ref, spec.v_min), spec.v_max)
    c_thresh = round((v_r - spec.v_min) / spec.lsb) - half
    # code*lsb + (v_min + half*lsb - v_ref) = ±v_mag  (bias cancels)
    off = spec.v_min + half * spec.lsb - v_ref
    c_pos = min(max(round((v_mag - off) / spec.lsb), lo), hi)
    c_neg = min(max(round((-v_mag - off) / spec.lsb), lo), hi)
    return c_thresh, c_pos, c_neg


def adc_quantize(v: jnp.ndarray, spec: ADCSpec = ADCSpec()) -> jnp.ndarray:
    """Uniform mid-rise ADC over [v_min, v_max] with STE gradients —
    the voltage-grid view (quantize-then-hold, no V_R - b subtraction),
    expressed on the same code grid as :func:`encode`."""
    half = spec.levels // 2
    q = (_code_grid(v, spec) + half) * spec.lsb + spec.v_min
    if spec.ste:
        # exact-forward STE: lin - stop_grad(lin) is identically 0.0, so the
        # value is q bit-for-bit while the gradient is the clip passthrough
        lin = jnp.clip(v, spec.v_min, spec.v_max)
        return q + (lin - jax.lax.stop_gradient(lin))
    return q


def digital_readout(
    out_v: jnp.ndarray,
    v_ref: float,
    bias: jnp.ndarray | float = 0.0,
    spec: ADCSpec = ADCSpec(),
) -> jnp.ndarray:
    """ADC conversion followed by the digital ``V_R - b`` subtraction.

    Defined as ``dequantize(digital_codes(out_v, ...))`` so the float and
    code paths are bit-identical by construction; ``spec.ste`` adds the
    straight-through residual (gradient 1 w.r.t. ``out_v`` inside the
    rails, 1 w.r.t. ``bias``) for the co-design studies.
    """
    codes = digital_codes(out_v, v_ref, bias, spec)
    deq = dequantize(*codes)
    if spec.ste:
        # exact-forward STE (value is deq bit-for-bit — the wire contract
        # dequantize(digital_codes(v)) == digital_readout(v) is exact):
        # lin - stop_grad(lin) contributes 0.0 to the value and the
        # straight-through gradient (clip passthrough w.r.t. out_v; the
        # bias gradient arrives through ``zero`` inside deq).
        lin = jnp.clip(out_v, spec.v_min, spec.v_max)
        return deq + (lin - jax.lax.stop_gradient(lin))
    return deq
