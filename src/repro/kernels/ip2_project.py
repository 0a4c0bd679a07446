"""Pallas TPU kernel — the IP2 analog patch-projection array's digital twin.

TPU adaptation of the paper's in-pixel compute fabric (DESIGN.md §2): the
analog array performs, for a bank of patches in parallel,

    Out[p, v] = VR + droop * (sum_i PWM(P[p,i]) * Wq[i,v]) / N2
    feat[p, v] = ADC(NL(Out[p, v])) - (VR - bias[v])

One pallas grid step computes one (patch-bank x vector-bank) macro-op —
the moral equivalent of one charge-share/readout cycle — with:

  * activations PWM-quantized at tile load (the pixel->pulse-width
    converter lives next to the data, not in a separate pass);
  * the MXU doing the W x P multiply-accumulate (K-tiled, fp32 scratch
    accumulator in VMEM);
  * the full analog epilogue (charge-share /N2, OpAmp droop, 2T clip,
    edge-ADC quantization, VR-b digital subtraction) fused into the final
    K step, so features never round-trip to HBM in analog form.

Block sizes default to MXU-aligned (128) tiles; the wrapper in ops.py pads
inputs so every dimension divides its block.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import adc as adc_mod


@dataclasses.dataclass(frozen=True)
class IP2KernelParams:
    """Static analog-model constants baked into the kernel."""

    n2: int                      # true pixels/patch (charge-share divisor)
    pwm_levels: int = 64         # 6-bit PWM
    droop: float = 1.0           # summer retention factor (OpAmp: ~A0/(1+A0))
    v_ref: float = 0.0
    nl_kind: str = "none"        # "none" | "relu" (2T stage), clip at v_sat
    v_sat: float = 1.0
    adc_bits: int = 8
    adc_vmin: float = -1.0
    adc_vmax: float = 1.0
    adc_enable: bool = True
    adc_out_codes: bool = False  # emit int codes (the wire format, DESIGN.md §9)
    readout: str = "adc"         # "adc" | "sign" — epilogue mode (DESIGN.md §13)

    def __post_init__(self):
        if self.readout not in ("adc", "sign"):
            raise ValueError(f"unknown readout mode {self.readout!r}")

    def adc_spec(self) -> adc_mod.ADCSpec:
        return adc_mod.ADCSpec(
            bits=self.adc_bits, v_min=self.adc_vmin, v_max=self.adc_vmax
        )

    @property
    def out_dtype(self):
        if self.readout == "sign":
            return jnp.int8  # {0,1} sign bits; the wrapper re-types to bool
        if self.adc_enable and self.adc_out_codes:
            return self.adc_spec().code_dtype
        return jnp.float32


def pwm_quantize_tile(x: jnp.ndarray, p: IP2KernelParams) -> jnp.ndarray:
    """Pixel -> pulse width on the PWM clock grid (time quantization),
    applied at tile load so the converter lives next to the data."""
    n = p.pwm_levels - 1
    return jnp.round(jnp.clip(x, 0.0, 1.0) * n) * (1.0 / n)


def analog_epilogue_tile(acc: jnp.ndarray, b: jnp.ndarray, p: IP2KernelParams) -> jnp.ndarray:
    """The fused analog readout: charge-share /N2 + droop + VR, the 2T
    nonlinearity, then one of the mode-selectable conversion epilogues
    (DESIGN.md §13). Shared by the dense, sparse, ragged and fused kernels
    — ``p.readout`` is static, so the default ``"adc"`` path lowers to
    exactly the pre-mode pipeline (asserted bitwise in tests).

    * ``readout="adc"`` (default) — the edge ADC. With ``adc_out_codes``
      the tile leaves in wire format — centered integer code values (cast
      to the code dtype by the caller); the bias is NOT applied (it lives
      in the ``zero`` metadata of
      :func:`repro.core.adc.readout_scale_zero`). Otherwise the
      dequantized float readout including the VR-b digital subtraction is
      produced, on exactly the grid of
      :func:`repro.core.adc.digital_readout` so kernel and jnp paths stay
      bit-identical.
    * ``readout="sign"`` — ADC-less comparator readout: one bit per
      vector, ``out >= V_R``, emitted as {0, 1} on the f32 grid (the
      caller casts to int8; the ops wrapper re-types the wire to bool).
      As on the code wire, the bias is metadata
      (:func:`repro.core.adc.sign_scale_zero`), never payload.
    """
    out = acc * (p.droop / p.n2) + p.v_ref
    if p.nl_kind == "relu":
        out = jnp.clip(out, 0.0, p.v_sat)
    if p.readout == "sign":
        return jnp.where(out >= p.v_ref, 1.0, 0.0)
    if not p.adc_enable:
        return out - (p.v_ref - b)
    spec = p.adc_spec()
    code = adc_mod._code_grid(out, spec)           # f32 centered codes
    if p.adc_out_codes:
        return code
    scale, zero = adc_mod.readout_scale_zero(p.v_ref, b, spec)
    return adc_mod.dequantize(code, scale, zero, code_bits=spec.bits)


def _ip2_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, p: IP2KernelParams, k_steps: int):
    """Grid = (patch banks, vector banks, K banks); K innermost/arbitrary."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xq = pwm_quantize_tile(x_ref[...], p)
    acc_ref[...] += jnp.dot(xq, w_ref[...], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        o_ref[...] = analog_epilogue_tile(acc_ref[...], b_ref[...], p).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("params", "block_p", "block_m", "block_k", "interpret"),
)
def ip2_project_pallas(
    patches: jnp.ndarray,      # (P, K) pixel voltages in [0,1]; K = padded N2
    w_q: jnp.ndarray,          # (K, M) DAC-quantized weights (pre-quantized)
    bias: jnp.ndarray,         # (1, M) — 2-D so Mosaic tiles it like the output
    params: IP2KernelParams,
    block_p: int = 128,
    block_m: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Padded-shape kernel entry; use repro.kernels.ops.ip2_project."""
    P, K = patches.shape
    K2, M = w_q.shape
    assert K == K2 and bias.shape == (1, M)
    assert P % block_p == 0 and M % block_m == 0 and K % block_k == 0, (
        f"pad shapes to blocks: {(P, K, M)} vs {(block_p, block_k, block_m)}"
    )
    k_steps = K // block_k
    grid = (P // block_p, M // block_m, k_steps)

    return pl.pallas_call(
        functools.partial(_ip2_kernel, p=params, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_p, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_m), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_m), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_p, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((P, M), params.out_dtype),
        scratch_shapes=[pltpu.VMEM((block_p, block_m), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(patches, w_q, bias)
