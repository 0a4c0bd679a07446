"""IP2Frontend — the full sensor-to-features path (paper Fig. 1/2).

scene RGB -> lenslet/optics AA filter -> Bayer mosaic -> CDS sample
          -> salient patch selection (<=25 %) -> analog patch projection
          (PWM x switched-cap, M vectors/patch) -> edge ADC -> digital
          features + V_R - b subtraction.

Two selectable paths compute the projection:

* ``analog=True``  — the paper's circuit: Bayer single-channel patches,
  A' = strike_columns(A), PWM/DAC quantization, charge-share /N², droop,
  optional 2T nonlinearity, edge ADC. This is the hardware digital twin.
* ``analog=False`` — the float "algorithm simulation" the paper trains
  against: full-RGB patches through the unquantized matrix A.

And two execution modes select the dataflow (see DESIGN.md §3 for when to
choose each):

* ``mode="dense"``   — project every patch, then zero-mask the deselected
  ones. Features keep the full (..., P, M) grid shape; used for training
  and the accuracy/bits/active-fraction co-design studies where gradients
  must reach every patch position.
* ``mode="compact"`` — *select -> gather -> project*: only the (exactly k)
  active patches are gathered ahead of the projection, so analog compute,
  ADC conversions and streamed features all scale with the active
  fraction — the dataflow the hardware actually implements and the source
  of the paper's 10x bandwidth / <30 mW/MP claims. Returns static-shape
  (..., k, M) features plus the patch indices.

Compact-mode output on the analog path is the digital WIRE FORMAT by
default (DESIGN.md §9): int8 ADC codes plus static (scale, zero) dequant
metadata — what the hardware actually streams, 4x fewer bytes than
float32 — dequantized in exactly one place, the backend's first matmul
(:func:`dequantize_features`). ``wire="float"`` selects the
bit-identical STE float view instead. The float simulation
(``analog=False``) has no edge ADC and therefore no code wire: its
compact payload resolves to the (unquantized) float view.

Both the dense path and the float-wire compact path are differentiable
(STE through the quantizers; the compact gather is a differentiable
take), enabling the co-design studies of §1 and §2.1.3 on either
dataflow; integer codes carry no gradients, so training uses those views.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import adc as adc_mod
from repro.core import bayer as bayer_mod
from repro.core import power as power_mod
from repro.core import projection as proj_mod
from repro.core import saliency as sal_mod
from repro.core import temporal as temporal_mod


@dataclasses.dataclass(frozen=True)
class FrontendConfig:
    image_h: int = 256
    image_w: int = 256
    patch: proj_mod.PatchSpec = proj_mod.PatchSpec(patch_h=32, patch_w=32, n_vectors=400)
    analog: bool = True
    bayer: bool = True                 # raw mosaic input (HW); False = RGB (sim)
    aa_cutoff: float | None = 0.5      # Gaussian AA at 0.5/0.25 Nyquist; None = off
    active_fraction: float = 0.25
    adc: adc_mod.ADCSpec = adc_mod.ADCSpec()
    temporal: temporal_mod.TemporalSpec = temporal_mod.TemporalSpec()

    @property
    def grid(self) -> tuple[int, int]:
        return (self.image_h // self.patch.patch_h, self.image_w // self.patch.patch_w)

    @property
    def n_patches(self) -> int:
        gh, gw = self.grid
        return gh * gw

    @property
    def n_active(self) -> int:
        return max(1, int(round(self.n_patches * self.active_fraction)))


class CompactFeatures(NamedTuple):
    """The bandwidth-true frontend output: only active patches exist, in
    the digital wire format (DESIGN.md §9).

    ``features[..., i, :]`` is the ADC conversion of patch
    ``indices[..., i]`` — by default the raw int8 ADC *codes* (exactly
    what the hardware streams off-sensor; ``features.nbytes`` IS the
    per-frame wire traffic), or the float32 STE readout under the
    ``wire="float"`` training/diagnostic path. ``valid[..., i]`` is False
    only when fewer than k patches were active and slot i is a repeated
    filler (never the case when selection comes from the exactly-k
    index-first API).

    ``scale``/``zero`` are the static affine dequant metadata (ADC LSB and
    ``v_min + half·lsb - V_R + bias``); ``gain`` is the per-token
    digital-side multiplier (valid mask × held-charge droop ``d^age``;
    identically 1.0 on fresh valid conversions). The ONE place these may
    be folded into the payload is :func:`dequantize_features` — the
    backend's first matmul (DESIGN.md §9).

    ``energy`` is the in-pixel patch-energy proxy over the FULL grid — an
    analog-domain signal (the photodiodes integrate light regardless of
    selection, so it is free) that never crosses the feature wire; the
    saccade loop consumes it from here instead of re-running
    :func:`sensor_patches` (DESIGN.md §5).

    ``events`` is this frame's executed energy-event ledger
    (:class:`repro.core.power.EventCounts`, per batch element; DESIGN.md
    §10): the ADC conversions / cap charges / DAC loads / CDS samples /
    comparator+OpAmp windows that the frontend ACTUALLY spent producing
    this payload — ``k·M`` conversions on the ungated compact path,
    ``n_stale·M`` under the temporal gate (holds are free). Price it
    with :class:`repro.core.power.EnergyMeter`. Like ``energy``, it is
    O(1) metadata, never part of the wire payload.
    """

    features: jnp.ndarray   # (..., k, M) int8 ADC codes (or f32, wire="float")
    indices: jnp.ndarray    # (..., k) int32 patch indices
    valid: jnp.ndarray      # (..., k) bool
    energy: jnp.ndarray     # (..., P) float32 patch-energy proxy (analog domain)
    scale: jnp.ndarray      # () float32 — ADC LSB (volts per code)
    zero: jnp.ndarray       # (M,) float32 — dequant offset incl. V_R - b
    gain: jnp.ndarray       # (..., k) float32 — valid × droop d^age
    events: power_mod.EventCounts = power_mod.EventCounts()  # (...,) leaves


def dequantize_features(cf: CompactFeatures) -> jnp.ndarray:
    """The one permitted dequant site (DESIGN.md §9): codes -> float32
    readout via the static affine, times the per-token ``gain`` (valid
    mask and held-charge droop). Float-wire payloads skip the affine —
    on the analog path they are already the (bit-identical) dequantized
    readout, so both wires produce the same floats here."""
    feats = cf.features
    if not jnp.issubdtype(feats.dtype, jnp.floating):
        feats = adc_mod.dequantize(feats, cf.scale, cf.zero)
    return feats * cf.gain[..., None]


def init_frontend_params(key: jax.Array, cfg: FrontendConfig) -> dict:
    """A is always trained in vectorized-RGB space (M, N²·3); the analog path
    strikes columns to A' at apply time (paper §2.1.5).

    Full-scale matching (co-design): the charge-share sum divides by N², so
    the weight DAC full-scale current must be ~√N² larger than a classic
    1/√fan_in init or the OpAmp output sits below one ADC LSB and the edge
    ADC quantizes every feature to zero. σ_W = 0.4·√N² puts Out_v's std at
    ≈0.25 of the ±1 V rail (pixels ~U[0,1], A' keeps N² of the 3N² cols).
    """
    n2 = cfg.patch.pixels_per_patch
    m = cfg.patch.n_vectors
    scale = 0.4 * jnp.sqrt(jnp.asarray(n2, jnp.float32))
    a = jax.random.normal(key, (m, n2 * 3), jnp.float32) * scale
    return {"a_rgb": a, "bias": jnp.zeros((m,), jnp.float32)}


ProjectFn = Callable[[jnp.ndarray, jnp.ndarray, proj_mod.PatchSpec], jnp.ndarray]


def _call_project_fn(fn, patches, weights, spec, row_counts):
    """Invoke a ProjectFn, forwarding the ragged per-slot row counts only
    to adapters that advertise ``supports_row_counts`` (DESIGN.md §11) —
    plain callables keep the original 3-arg signature. ``row_counts`` is
    DATA (no recompile); rows at positions >= their slot's count come back
    ZERO from a ragged adapter, so callers must only pass counts when the
    tail rows are discarded (temporal gate) or gained out (k_cap shed)."""
    if row_counts is not None and getattr(fn, "supports_row_counts", False):
        return fn(patches, weights, spec, row_counts=row_counts)
    return fn(patches, weights, spec)


def sensor_patches(
    params: dict, rgb: jnp.ndarray, cfg: FrontendConfig
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Optics + mosaic + patch extraction: rgb (..., H, W, 3) ->
    (patches (..., P, N), effective weights (M, N)).

    This is the part of the frontend that is physically unavoidable — every
    photodiode integrates light regardless of selection — and therefore
    shared verbatim by the dense and compact dataflows.
    """
    p = cfg.patch
    if cfg.analog or cfg.bayer:
        if cfg.aa_cutoff is None:
            frame = bayer_mod.mosaic(rgb)                            # (..., H, W)
        else:
            from repro.kernels import ops  # lazy: keep the core import-light

            # optics and mosaic in one kernel pass over the frame
            frame = ops.bayer_frame(rgb, cfg.aa_cutoff)
        patches = proj_mod.extract_patches(frame, p.patch_h, p.patch_w)
        weights = bayer_mod.strike_columns(params["a_rgb"], p.patch_h, p.patch_w)
    else:
        # float simulation path: vectorized RGB patches
        if cfg.aa_cutoff is not None:
            rgb = bayer_mod.antialias(rgb, cfg.aa_cutoff, channels_last=True)
        per_c = [
            proj_mod.extract_patches(rgb[..., c], p.patch_h, p.patch_w) for c in range(3)
        ]
        patches = jnp.concatenate(per_c, axis=-1)                    # (..., P, N²·3)
        weights = params["a_rgb"]

    return patches, weights


def project_readout(
    patches: jnp.ndarray,
    weights: jnp.ndarray,
    params: dict,
    cfg: FrontendConfig,
    project_fn: ProjectFn | None,
    row_counts=None,
) -> jnp.ndarray:
    """Analog projection + edge ADC (or the float simulation) over whatever
    set of patches it is handed — the full grid (dense) or the gathered
    active set (compact). Float view: ``digital_readout`` is the STE
    dequant of the ADC codes, bit-identical to the code wire by
    construction (DESIGN.md §9). ``row_counts`` rides to ragged-capable
    kernel adapters only (see :func:`_call_project_fn`)."""
    if project_fn is not None and getattr(project_fn, "emits_codes", False):
        raise ValueError(
            "project_fn emits wire-format codes (ops.ip2_codes_fn) but this "
            "is a float path (dense mode or wire='float'): its int8 output "
            "is not analog voltage. Use ops.ip2_project_fn here, or "
            "mode='compact' with wire='codes'."
        )
    if project_fn is not None and getattr(project_fn, "emits_sign", False):
        raise ValueError(
            "project_fn emits the 1-bit sign wire (ops.ip2_sign_fn) but "
            "this is a float path (dense mode or wire='float'): its bool "
            "output is not analog voltage. Use ops.ip2_project_fn here, or "
            "mode='compact' with wire='sign'."
        )
    if cfg.analog:
        fn = project_fn or proj_mod.analog_project_patches
        out_v = _call_project_fn(fn, patches, weights, cfg.patch, row_counts)
        return adc_mod.digital_readout(out_v, cfg.patch.summer.v_ref, params["bias"], cfg.adc)
    n_in = patches.shape[-1]
    return jnp.einsum("...pi,vi->...pv", patches, weights) / n_in + params["bias"]


def feature_scale_zero(
    params: dict, cfg: FrontendConfig
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The static (scale, zero) dequant metadata of this frontend's wire
    format — a function of (ADCSpec, V_R, bias) only, never of the frame."""
    return adc_mod.readout_scale_zero(
        cfg.patch.summer.v_ref, params["bias"], cfg.adc
    )


def project_wire(
    patches: jnp.ndarray,
    weights: jnp.ndarray,
    params: dict,
    cfg: FrontendConfig,
    project_fn: ProjectFn | None,
    wire: str,
    row_counts=None,
) -> jnp.ndarray:
    """Project a gathered patch set onto the requested wire format.

    ``wire="codes"`` (analog only — the float simulation has no ADC, so
    there are no codes to emit): int8 ADC codes — from the kernel's fused
    epilogue when ``project_fn`` advertises ``emits_codes`` (the
    conversion happens exactly once, at the array edge, inside the
    kernel), else by encoding the analog output here.

    ``wire="float"``: the STE dequant view (differentiable; on the analog
    path, bit-identical values to dequantizing the codes).

    ``wire="sign"`` (analog only, DESIGN.md §13): the ADC-less 1-bit
    comparator wire — bool payload, from the kernel's sign epilogue when
    ``project_fn`` advertises ``emits_sign`` (``ops.ip2_sign_fn``), else
    by comparing the analog output against V_R here.

    ``row_counts`` (DESIGN.md §11): per-slot real-row counts forwarded to
    ragged-capable kernel adapters so rows past the count cost zero
    FLOPs/bytes instead of masked-but-computed work; other projectors
    ignore it (they compute every handed row).
    """
    if wire == "float":
        return project_readout(
            patches, weights, params, cfg, project_fn, row_counts=row_counts)
    if not cfg.analog:
        raise ValueError(
            f"wire={wire!r} requires analog=True: the float simulation has "
            "no edge ADC or comparator, so there is no digital wire — use "
            "wire='float' (the default resolution for analog=False)"
        )
    if wire == "sign":
        if project_fn is not None and getattr(project_fn, "emits_codes", False):
            raise ValueError(
                "project_fn emits wire-format ADC codes (ops.ip2_codes_fn) "
                "but wire='sign' carries 1-bit comparator output — use "
                "ops.ip2_sign_fn (or a plain projector) here"
            )
        if project_fn is not None and getattr(project_fn, "emits_sign", False):
            return _call_project_fn(
                project_fn, patches, weights, cfg.patch, row_counts)
        fn = project_fn or proj_mod.analog_project_patches
        out_v = _call_project_fn(fn, patches, weights, cfg.patch, row_counts)
        return adc_mod.sign_encode(out_v, cfg.patch.summer.v_ref)
    if project_fn is not None and getattr(project_fn, "emits_sign", False):
        raise ValueError(
            "project_fn emits the 1-bit sign wire (ops.ip2_sign_fn) but "
            "wire='codes' carries int8 ADC codes — use ops.ip2_codes_fn "
            "(or a plain projector) here"
        )
    if project_fn is not None and getattr(project_fn, "emits_codes", False):
        return _call_project_fn(
            project_fn, patches, weights, cfg.patch, row_counts)
    fn = project_fn or proj_mod.analog_project_patches
    out_v = _call_project_fn(fn, patches, weights, cfg.patch, row_counts)
    return adc_mod.encode(out_v, cfg.adc)


class CompactSelection(NamedTuple):
    """The resolved compact selection, before any projection is spent:
    the dense CDS patch voltages and effective weights from
    :func:`sensor_patches`, the exactly-k ranked patch indices, their
    prefix validity mask (``valid[..., i]`` implies ``valid[..., i-1]`` —
    fillers and governor-shed slots always trail), and the free
    analog-domain patch-energy proxy. This is the input contract of both
    the staged compact path (``apply_frontend(mode="compact")``) and the
    fused megakernel path (``vit_forward_compact`` with
    ``fused_embed=True``, DESIGN.md §11)."""

    patches: jnp.ndarray    # (..., P, N) dense CDS patch voltages
    weights: jnp.ndarray    # (M, N) effective projection weights
    indices: jnp.ndarray    # (..., k) int32 ranked patch indices
    valid: jnp.ndarray      # (..., k) bool prefix mask
    energy: jnp.ndarray     # (..., P) float32 patch-energy proxy


def select_compact(
    params: dict,
    rgb: jnp.ndarray,
    cfg: FrontendConfig,
    mask: jnp.ndarray | None = None,
    indices: jnp.ndarray | None = None,
    precomputed: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    k_cap: jnp.ndarray | None = None,
) -> CompactSelection:
    """Resolve the compact selection (select, do not yet project): sensor
    stage, patch energy, exactly-k indices with the same precedence as
    :func:`apply_frontend` (``indices`` > ``mask`` > energy top-k), and
    the governor's ``k_cap`` shed applied to the validity prefix.
    Shared by the staged and fused compact paths so their selections are
    identical by construction."""
    if k_cap is not None and mask is not None and indices is None:
        raise ValueError(
            "k_cap sheds trailing selection slots and therefore needs a "
            "selection ranked most-salient-first; mask-derived indices "
            "come out in ascending patch order (indices_from_mask), so "
            "the shed tokens would be arbitrary — pass ranked indices "
            "instead (see topk_patch_indices)"
        )
    k = cfg.n_active
    if precomputed is not None:
        patches, weights = precomputed
    else:
        patches, weights = sensor_patches(params, rgb, cfg)
    energy = sal_mod.patch_energy(patches)
    if indices is not None:
        idx = indices.astype(jnp.int32)
        if idx.shape[-1] != k:
            raise ValueError(f"indices last dim {idx.shape[-1]} != n_active {k}")
        valid = jnp.ones(idx.shape, bool)
    elif mask is not None:
        idx, valid = sal_mod.indices_from_mask(mask, k)
    else:
        idx = sal_mod.topk_patch_indices(energy, k)
        valid = jnp.ones(idx.shape, bool)
    if k_cap is not None:
        # governor k-tier: selection indices are score-ranked, so shedding
        # the trailing slots keeps exactly the top-k_cap tokens (data-only:
        # same shapes, capped tokens flagged invalid and served as zero)
        valid = valid & (jnp.arange(k) < k_cap[..., None])
    return CompactSelection(patches, weights, idx, valid, energy)


def apply_frontend(
    params: dict,
    rgb: jnp.ndarray,
    cfg: FrontendConfig,
    mask: jnp.ndarray | None = None,
    project_fn: ProjectFn | None = None,
    mode: str = "dense",
    indices: jnp.ndarray | None = None,
    precomputed: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    cache: temporal_mod.FeatureCache | None = None,
    wire: str | None = None,
    k_cap: jnp.ndarray | None = None,
    stale_cap: jnp.ndarray | None = None,
):
    """rgb (..., H, W, 3) in [0,1] -> frontend features.

    Selection inputs (the backend's saccadic prediction for this frame):
    ``indices`` (..., k) takes precedence, then ``mask`` (..., P); if both
    are None a patch-energy top-k stand-in is used. ``project_fn`` lets the
    Pallas kernel replace the reference einsum (same signature/semantics;
    a kernel adapter advertising ``emits_codes`` — ``ops.ip2_codes_fn`` —
    emits the wire format straight from its fused ADC epilogue).
    ``precomputed`` is an optional ``(patches, weights)`` pair from an
    earlier :func:`sensor_patches` call on the same frame, so callers that
    already needed the CDS patch voltages (e.g. the serving engine's
    in-step bootstrap) don't pay for the optics/mosaic stage twice.

    ``wire`` (compact mode only) selects the payload format of
    :class:`CompactFeatures` (DESIGN.md §9): ``"codes"`` — int8 ADC
    codes, what the hardware streams, 4x fewer bytes; ``"float"`` — the
    STE dequant view, bit-identical values after
    :func:`dequantize_features`, differentiable for compact-path
    co-design. ``None`` (default) resolves per config: ``"codes"`` when
    ``cfg.analog`` (there is a real edge ADC) and ``"float"`` for the
    float simulation (``analog=False`` — no ADC, no code wire; requesting
    ``"codes"`` there raises).

    ``cache`` (compact mode only) enables the temporal delta gate
    (DESIGN.md §6): of the k selected patches, only the stale subset —
    CDS energy moved by >= ``cfg.temporal.delta_threshold`` since last
    recompute, never computed, or drooped past the LSB budget — is
    gathered/projected/converted (exactly ``cfg.temporal`` budget-j slots,
    static shape); the rest are served from the held charge modelled by
    the cache. The cache dtype must match the wire (code caches for
    ``wire="codes"``). The return value becomes
    ``(CompactFeatures, FeatureCache)``.

    ``k_cap`` / ``stale_cap`` (compact mode only) are the power
    governor's per-stream DATA knobs (DESIGN.md §10) — neither changes a
    shape, so governing never recompiles. ``k_cap`` (..., ) int32 marks
    selection slots ``>= k_cap`` invalid (the tokens are shed: not
    served, not converted, their patches dump like deselected ones);
    ``stale_cap`` (..., ) int32 truncates the temporal gate's needed set
    to its first ``stale_cap`` ranked slots (requires ``cache``). Both
    are bitwise no-ops at ``k_cap >= k`` / ``stale_cap >= j``.

    ``k_cap`` sheds TRAILING slots, so it requires a selection ranked
    most-salient-first: the default energy top-k and the engine's
    score top-k are; caller-supplied ``indices`` must be (as
    ``topk_patch_indices`` emits them). ``mask``-derived selections come
    out in ascending patch order — shedding their tail would drop
    arbitrary patches, not the least salient — so that combination
    raises.

    Returns (mode="dense"):   (features (..., P, M), mask (..., P)) with
      deselected patches zeroed — compute scales with P. Always float
      (the STE training path); ``wire`` does not apply.
    Returns (mode="compact"): :class:`CompactFeatures` with (..., k, M)
      features — compute scales with k (select -> gather -> project);
      with ``cache`` given, ``(CompactFeatures, FeatureCache)`` and
      per-frame projection/ADC work scales with the recompute budget j.
    """
    if mode not in ("dense", "compact"):
        raise ValueError(f"mode must be 'dense' or 'compact', got {mode!r}")
    if wire is None:
        wire = "codes" if cfg.analog else "float"
    if wire not in ("codes", "float", "sign"):
        raise ValueError(
            f"wire must be 'codes', 'float' or 'sign', got {wire!r}")
    if cache is not None and mode != "compact":
        raise ValueError(
            "the temporal cache only applies to mode='compact'; dense "
            "(training) execution must bypass it — see DESIGN.md §6"
        )
    if (k_cap is not None or stale_cap is not None) and mode != "compact":
        raise ValueError(
            "k_cap/stale_cap are governor knobs of the compact serving "
            "path (DESIGN.md §10); dense execution has no gate to cap"
        )
    if stale_cap is not None and cache is None:
        raise ValueError(
            "stale_cap caps the temporal gate's recompute allocation; "
            "pass a FeatureCache (there is no gate to cap without one)"
        )
    if k_cap is not None and mask is not None and indices is None:
        raise ValueError(
            "k_cap sheds trailing selection slots and therefore needs a "
            "selection ranked most-salient-first; mask-derived indices "
            "come out in ascending patch order (indices_from_mask), so "
            "the shed tokens would be arbitrary — pass ranked indices "
            "instead (see topk_patch_indices)"
        )
    if precomputed is not None:
        patches, weights = precomputed
    else:
        patches, weights = sensor_patches(params, rgb, cfg)

    if mode == "dense":
        if indices is not None:                  # same precedence as compact
            mask = sal_mod.mask_from_indices(indices, cfg.n_patches)
        elif mask is None:
            mask = sal_mod.topk_patch_mask(
                sal_mod.patch_energy(patches), cfg.active_fraction
            )
        feats = project_readout(patches, weights, params, cfg, project_fn)
        return sal_mod.apply_patch_mask(feats, mask), mask

    # compact: resolve the selection to exactly-k indices, gather the active
    # patches, and only then spend analog compute / ADC conversions on them.
    k = cfg.n_active
    sel = select_compact(
        params, rgb, cfg, mask=mask, indices=indices,
        precomputed=(patches, weights), k_cap=k_cap,
    )
    idx, valid, energy = sel.indices, sel.valid, sel.energy

    n_pixels = float(cfg.image_h * cfg.image_w)
    n_selected = jnp.sum(valid, axis=-1).astype(jnp.float32)
    # sign wire: 1-bit payload, ±v_mag reconstruction affine (DESIGN.md
    # §13); its conversions are comparator firings, not ADC conversions
    readout = "sign" if wire == "sign" else "adc"
    if wire == "sign":
        scale, zero = adc_mod.sign_scale_zero(params["bias"])
    else:
        scale, zero = feature_scale_zero(params, cfg)
    if cache is None:
        active = sal_mod.gather_patches(patches, idx)                # (..., k, N)
        # governed streams hand ragged-capable kernels the per-slot valid
        # count (valid is a prefix): shed tokens then cost zero FLOPs and
        # zero VMEM traffic instead of compute-then-gain-to-zero. Shed
        # rows come back as zero payload — identical after gain either way.
        row_counts = (
            jnp.sum(valid, axis=-1).astype(jnp.int32)
            if k_cap is not None else None
        )
        payload = project_wire(
            active, weights, params, cfg, project_fn, wire,
            row_counts=row_counts)
        gain = valid.astype(jnp.float32)
        # ungated compact path: every served token was projected AND
        # converted this frame — n_selected·M real ADC conversions
        events = power_mod.frontend_frame_events(
            n_pixels, cfg.patch.pixels_per_patch, cfg.patch.n_vectors,
            n_selected_patches=n_selected, n_converted_patches=n_selected,
            readout=readout,
        )
        return CompactFeatures(
            payload, idx, valid, energy, scale, zero, gain, events)

    # temporal delta gate: recompute only the stale subset of the selection,
    # scatter-merge into the held-charge cache, serve the selection from it
    # (raw payload + droop/charge gain; dequantize_features folds them).
    cdt = cache.features.dtype
    cache_ok = (
        jnp.issubdtype(cdt, jnp.floating) if wire == "float"
        else cdt == jnp.bool_ if wire == "sign"
        else jnp.issubdtype(cdt, jnp.signedinteger)
    )
    if not cache_ok:
        raise ValueError(
            f"cache dtype {cdt} does not match wire={wire!r}; "
            "build it with init_feature_cache(cfg, ..., dtype=...) to match"
        )
    tspec = cfg.temporal
    stale_idx, needed, n_stale = temporal_mod.select_stale(
        energy, idx, cache, tspec, cfg.patch.summer, cfg.adc,
        sel_valid=valid, cap=stale_cap,
    )
    stale_patches = sal_mod.gather_patches(patches, stale_idx)       # (..., j, N)
    # the needed set is ranked stale-first, so n_stale is a prefix count:
    # ragged-capable kernels skip the (j - n_stale) filler rows entirely
    # (their zeroed outputs are discarded — refresh merges needed rows only)
    new_feats = project_wire(
        stale_patches, weights, params, cfg, project_fn, wire,
        row_counts=n_stale.astype(jnp.int32))
    cache = temporal_mod.refresh(
        cache, stale_idx, needed, new_feats, energy, n_stale
    )
    payload = temporal_mod.take_rows(cache.features, idx)            # (..., k, M)
    gain = (
        temporal_mod.held_gain(cache, idx, cfg.patch.summer)
        * valid.astype(jnp.float32)
    )
    # gated path: only the n_stale recomputed patches paid for projection
    # and conversion — holds are free (non-destructive readout, §2.1.2)
    events = temporal_mod.gated_frame_events(
        n_pixels, cfg.patch.pixels_per_patch, cfg.patch.n_vectors,
        n_selected=n_selected, n_stale=n_stale.astype(jnp.float32),
        readout=readout,
    )
    return CompactFeatures(
        payload, idx, valid, energy, scale, zero, gain, events), cache


def compact_features(
    feats: jnp.ndarray, mask: jnp.ndarray, cfg: FrontendConfig
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Bandwidth-true view of already-computed dense features: gather the
    active patches. Prefer ``apply_frontend(..., mode="compact")``, which
    avoids computing the deselected patches in the first place."""
    return sal_mod.compact_active(feats, mask, cfg.n_active)
