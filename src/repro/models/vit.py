"""IP2-ViT: the paper's backend — patch-token transformer classifier fed by
the IP2 analog frontend (paper §1: "transformer-based backend model for
object classification and detection").

Pipeline per frame:
  RGB scene -> IP2Frontend (AA optics, Bayer, salient-patch analog
  projection, edge ADC) -> per-patch M-dim features == tokens
  -> linear embed -> transformer encoder (optionally with Fig. 4 QTH
  power-of-2 attention) -> masked mean-pool over ACTIVE patches -> classes.

Two token layouts feed the same weights (DESIGN.md §4):

* ``vit_forward``          — dense (..., P) token grid with deselected
  patches zero-masked; attention keys are restricted to the active set
  (a powered-down patch stores no charge, so it cannot be attended to).
  Used for training / co-design, where gradients need the full grid.
* ``vit_forward_compact``  — exactly the k active tokens, positional
  embeddings looked up by patch index. Attention cost drops from O(P²) to
  O(k²) (~16x fewer score FLOPs at 25 % activity; ~4x fewer tokens), and
  the two layouts produce identical logits for the same selection.

The compact forward also returns the per-patch attention the backend paid
to each token — the saccade signal that selects the next frame's patches.

The frontend is differentiable (STE quantizers), so the co-design loop
trains A (the in-pixel weights) jointly with the backend — the study the
paper describes in §1/§2.1.3.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core import adc as adc_mod
from repro.core import power as power_mod
from repro.core.frontend import (
    CompactFeatures,
    FrontendConfig,
    apply_frontend,
    dequantize_features,
    feature_scale_zero,
    init_frontend_params,
    select_compact,
)
from repro.core.projection import PatchSpec
from repro.models.layers import DEFAULT_PLAN, apply_mlp, dense_init, init_mlp, rms_norm
from repro.models.attention import init_attention
from repro.configs.base import ModelConfig
from repro.core.qth_attention import QTHSpec, qth_attention_weights

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    frontend: FrontendConfig = FrontendConfig()
    n_classes: int = 4
    n_layers: int = 4
    d_model: int = 128
    n_heads: int = 4
    d_ff: int = 256
    qth: bool = False          # Fig. 4 power-of-2 attention in the backend
    quant_embed: bool = False  # consume ADC codes via the w8a8 kernel (§9)
    fused_embed: bool = False  # frontend megakernel: project + ADC + embed
                               # in one kernel, codes never leave VMEM (§11);
                               # requires quant_embed and an analog frontend
    saliency_layers: str = "all"  # which layers' attention feeds saccade
                                  # saliency: "all" (mean, the original
                                  # contract) or "last" — serving layers
                                  # before the last then skip materializing
                                  # the (B, H, q, s) probs tensor entirely
    delta_kernel: bool = False    # delta-gated backend only (§14): score
                                  # stale query prefixes with the ragged
                                  # Pallas kernel on layers whose probs are
                                  # not needed (pairs with
                                  # saliency_layers="last"; qth excluded)
    norm_eps: float = 1e-5

    def backbone_cfg(self) -> ModelConfig:
        return ModelConfig(
            name="ip2-vit-backbone", family="vision",
            n_layers=self.n_layers, d_model=self.d_model,
            n_heads=self.n_heads, n_kv_heads=self.n_heads,
            d_ff=self.d_ff, vocab=0, head_dim=self.d_model // self.n_heads,
            mlp_kind="gelu", qkv_bias=True, remat=False,
        )


def vit_config_from(model: ModelConfig, frontend_kw: dict | None = None,
                    **vit_kw) -> ViTConfig:
    """A :class:`ViTConfig` at a registered vision config's published
    widths (``configs/ip2_vit.py``): square frames of
    ``sqrt(n_image_tokens)`` patches of ``ip2_patch`` pixels a side,
    ``ip2_vectors`` analog vectors per patch, and the trunk's depth and
    widths. ``frontend_kw`` sets the other FrontendConfig fields (active
    fraction, temporal gate) and ``vit_kw`` the other ViTConfig fields
    (serving modes, ``n_classes``)."""
    if model.vision_frontend != "ip2":
        raise ValueError(f"{model.name} has no IP2 frontend")
    side = math.isqrt(model.n_image_tokens)
    if side * side != model.n_image_tokens:
        raise ValueError(
            f"{model.name}: {model.n_image_tokens} image tokens is not a "
            f"square patch grid")
    if model.head_dim * model.n_heads != model.d_model:
        raise ValueError(
            f"{model.name}: head_dim {model.head_dim} x {model.n_heads} "
            f"heads != d_model {model.d_model}")
    image = side * model.ip2_patch
    fcfg = FrontendConfig(
        image_h=image, image_w=image,
        patch=PatchSpec(patch_h=model.ip2_patch, patch_w=model.ip2_patch,
                        n_vectors=model.ip2_vectors),
        **(frontend_kw or {}),
    )
    return ViTConfig(frontend=fcfg, n_layers=model.n_layers,
                     d_model=model.d_model, n_heads=model.n_heads,
                     d_ff=model.d_ff, **vit_kw)


def init_vit(key, cfg: ViTConfig) -> dict:
    bb = cfg.backbone_cfg()
    ks = jax.random.split(key, cfg.n_layers * 2 + 4)
    p = {
        "ip2": init_frontend_params(ks[0], cfg.frontend),
        "embed": dense_init(ks[1], cfg.frontend.patch.n_vectors, cfg.d_model),
        "pos": jax.random.normal(ks[2], (cfg.frontend.n_patches, cfg.d_model)) * 0.02,
        "layers": [],
        "final_norm": jnp.ones((cfg.d_model,)),
        "head": dense_init(ks[3], cfg.d_model, cfg.n_classes),
    }
    for i in range(cfg.n_layers):
        p["layers"].append({
            "norm1": jnp.ones((cfg.d_model,)),
            "attn": init_attention(ks[4 + 2 * i], bb, DEFAULT_PLAN),
            "norm2": jnp.ones((cfg.d_model,)),
            "mlp": init_mlp(ks[5 + 2 * i], cfg.d_model, cfg.d_ff, "gelu"),
        })
    return p


def _encoder_attention(
    lp: dict, h: jnp.ndarray, cfg: ViTConfig, token_valid: jnp.ndarray,
    need_probs: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """Bidirectional self-attention over the patch tokens (dense grid or
    compact active set — the sequence axis is whatever it is handed).

    The token sequence is short (P <= a few hundred, k a quarter of that),
    so scores are materialized explicitly; that also yields the attention
    probabilities the saccade loop feeds back as next-frame saliency.
    ``need_probs=False`` (a serving layer whose probs nobody reads)
    returns None in their place so XLA is free to fuse the whole
    softmax→mix chain instead of materializing the (B, H, q, s) tensor
    as a live output — the attention OUTPUT is bitwise identical either
    way (the arithmetic is unchanged; only the extra result is dropped).

    Returns (attn output (B, S, d), probs (B, H, S, S) or None).
    """
    dh = cfg.d_model // cfg.n_heads
    q = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wq"]) + lp["attn"]["bq"]
    k = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wk"]) + lp["attn"]["bk"]
    v = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wv"]) + lp["attn"]["bv"]
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(jnp.asarray(dh, h.dtype))
    if cfg.qth:
        # Fig. 4: power-of-2 quantized attention coefficients
        probs = qth_attention_weights(scores, QTHSpec(), key_valid=token_valid[:, None])
    else:
        scores = jnp.where(token_valid[:, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqs,bshk->bqhk", probs.astype(v.dtype), v)
    out = jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
    return out, (probs if need_probs else None)


def _encoder(
    params: dict, x: jnp.ndarray, cfg: ViTConfig, token_valid: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Transformer trunk + masked mean pool. Returns (logits, received):
    ``received`` (B, S) is the attention mass each token collected across
    heads/queries — mean over all layers (``cfg.saliency_layers="all"``,
    the original contract) or the last layer alone (``"last"``: earlier
    layers skip the probs materialization entirely; logits are bitwise
    unchanged, only the saliency estimate differs)."""
    if cfg.saliency_layers not in ("all", "last"):
        raise ValueError(
            f"saliency_layers must be 'all' or 'last', "
            f"got {cfg.saliency_layers!r}")
    n_layers = len(params["layers"])
    received = jnp.zeros(x.shape[:2], jnp.float32)
    qv = token_valid.astype(jnp.float32)
    n_q = jnp.maximum(jnp.sum(qv, axis=-1, keepdims=True), 1.0)
    for li, lp in enumerate(params["layers"]):
        need = (cfg.saliency_layers == "all") or (li == n_layers - 1)
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        out, probs = _encoder_attention(lp, h, cfg, token_valid,
                                        need_probs=need)
        x = x + out
        h = rms_norm(x, lp["norm2"], cfg.norm_eps)
        x = x + apply_mlp(lp["mlp"], h, "gelu")
        if need:
            # attention received per key token, averaged over heads and
            # the valid queries (invalid query rows emit garbage probs)
            per_key = jnp.einsum("bhqs,bq->bs", probs.astype(jnp.float32), qv)
            received = received + per_key / (n_q * probs.shape[1])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    # masked mean pool over the ACTIVE (ADC-converted) patches only
    w = token_valid.astype(x.dtype)[..., None]
    pooled = jnp.sum(x * w, axis=1) / jnp.maximum(jnp.sum(w, axis=1), 1.0)
    logits = pooled @ params["head"]
    if cfg.saliency_layers == "all":
        received = received / n_layers
    return logits, received


def vit_forward(params: dict, rgb: jnp.ndarray, cfg: ViTConfig,
                mask=None, return_aux: bool = False):
    """Dense path: rgb (B, H, W, 3) -> class logits (B, n_classes).

    With ``return_aux=True`` also returns ``{"mask", "saliency"}`` —
    ``saliency`` (B, P) is the backend attention each patch received
    (0 on deselected patches). Together with the selection and a
    ``patch_energy`` pass this lets the dense path act as a saccade
    oracle (see tests/test_system.py, which assembles the full
    ``saccade_scores`` aux from these pieces).
    """
    feats, mask = apply_frontend(params["ip2"], rgb, cfg.frontend, mask=mask)
    x = feats @ params["embed"] + params["pos"][None]
    logits, received = _encoder(params, x, cfg, mask)
    if not return_aux:
        return logits
    saliency = jnp.where(mask, received, 0.0)
    return logits, {"mask": mask, "saliency": saliency}


def prepare_quant_embed(params: dict) -> dict:
    """Serving-time weight prep for ``ViTConfig.quant_embed``: quantize the
    embed matrix to int8 ONCE (the DAC-programmed-once analogue, DESIGN.md
    §9) and stash it as ``params["embed_q"]`` so the hot serving step does
    not re-derive it every frame. Serving only — do not feed the returned
    params to an optimizer (``embed_q`` is frozen int8 prep, not a
    trainable leaf); re-run after any embed update."""
    from repro.kernels import ops  # lazy: keep the model import-light

    return {**params, "embed_q": ops.quantize_weights_int8(params["embed"])}


def _embed_tokens(params: dict, cf: CompactFeatures, cfg: ViTConfig) -> jnp.ndarray:
    """The backend's first matmul — the ONE place the wire format is
    dequantized (DESIGN.md §9).

    Default: fold the static affine into the payload
    (:func:`dequantize_features`) and matmul in float — bit-identical to
    the float-wire path. With ``cfg.quant_embed`` and a code payload, the
    codes feed the w8a8 kernel directly (``ops.quant_matmul_pre``): the
    edge ADC already performed the activation quantization, so there is no
    second rounding of activations — only the embed weights are quantized
    (int8 per-column, once via :func:`prepare_quant_embed` or per call as
    a fallback), and the affine distributes over the matmul:

        ((c·s + z) ⊙ g) @ W  =  g ⊙ (s·(c @ W8)·s_w + z @ dequant(W8))
    """
    feats = cf.features
    if feats.dtype == jnp.bool_:
        # ADC-less sign wire (DESIGN.md §13): a 1-bit payload with the
        # sign affine, NOT int8 codes with the code affine — it must not
        # enter the w8a8 kernel. Its dequant is the same one-site fold
        # ({0,1}·2v_mag + (bias - v_mag) = ±v_mag + bias), so the generic
        # route below is already exact.
        return dequantize_features(cf) @ params["embed"]
    if cfg.quant_embed and not jnp.issubdtype(feats.dtype, jnp.floating):
        from repro.kernels import ops  # lazy: keep the model import-light

        w8, s_w = params.get("embed_q") or ops.quantize_weights_int8(params["embed"])
        y = ops.quant_matmul_pre(feats, cf.scale, w8, s_w)
        zero_term = cf.zero @ (w8.astype(jnp.float32) * s_w[None, :])
        return (y + zero_term) * cf.gain[..., None]
    return dequantize_features(cf) @ params["embed"]


def _forward_compact_fused(
    params: dict,
    rgb: jnp.ndarray,
    cfg: ViTConfig,
    indices,
    mask,
    project_fn,
    precomputed,
    cache,
    wire,
    k_cap,
    stale_cap,
) -> tuple[jnp.ndarray, dict]:
    """The megakernel compact path (DESIGN.md §11): one Pallas kernel
    gathers the selected patches, projects, converts, and performs the
    w8a8 embed matmul — the staged select -> project -> wire ->
    ``_embed_tokens`` seam collapses and the int8 codes never leave VMEM.
    Logits are bitwise-equal the staged code-wire path for the same
    selection (tests/test_megakernel.py): the kernel's epilogue is the
    exact ``quant_matmul`` arithmetic and the affine/gain algebra below is
    the exact ``_embed_tokens`` expression."""
    fe_cfg = cfg.frontend
    if not cfg.quant_embed:
        raise ValueError(
            "fused_embed requires quant_embed=True: the megakernel's "
            "embed stage IS the w8a8 code consumption (DESIGN.md §9/§11)")
    if not fe_cfg.analog:
        raise ValueError(
            "fused_embed requires an analog frontend: the fused seam "
            "exists in ADC code space; the float simulation has no codes")
    if wire == "float":
        raise ValueError(
            "fused_embed has no float wire: codes are consumed in-kernel "
            "and never materialized — use fused_embed=False for the STE "
            "float view")
    if project_fn is not None:
        raise ValueError(
            "fused_embed IS the projector (one megakernel); a project_fn "
            "cannot be substituted into it — use fused_embed=False")
    if cache is not None or stale_cap is not None:
        raise ValueError(
            "fused_embed does not thread the temporal cache (held codes "
            "live outside the kernel); use fused_embed=False with a "
            "FeatureCache — the gated path reuses the same ragged "
            "machinery via row_counts=n_stale")
    from repro.kernels import ops  # lazy: keep the model import-light

    sel = select_compact(
        params["ip2"], rgb, fe_cfg,
        mask=mask, indices=indices, precomputed=precomputed, k_cap=k_cap,
    )
    # per-slot real-row count: valid is a prefix mask, so the ragged
    # megakernel skips shed/filler rows entirely (zero FLOPs/bytes)
    counts = jnp.sum(sel.valid, axis=-1).astype(jnp.int32)
    w8, s_w = params.get("embed_q") or ops.quantize_weights_int8(params["embed"])
    y = ops.ip2_fused_embed(
        sel.patches, sel.weights, sel.indices, fe_cfg.patch, fe_cfg.adc,
        w8, s_w, row_counts=counts,
    )
    scale, zero = feature_scale_zero(params["ip2"], fe_cfg)
    gain = sel.valid.astype(jnp.float32)
    # exactly _embed_tokens' affine: (y + zero @ dequant(W8)) * gain. Shed
    # rows are zero in y AND zero in gain — gain multiplies BEFORE the pos
    # add, so fused (never-computed) and staged (computed-then-gained-out)
    # rows land on identical x.
    x = (y + ops.fused_embed_zero_term(zero, w8, s_w)) * gain[..., None]
    x = x + params["pos"][sel.indices]
    logits, received = _encoder(params, x, cfg, sel.valid)

    n_selected = jnp.sum(sel.valid, axis=-1).astype(jnp.float32)
    # same ungated-compact ledger as apply_frontend: every served token
    # was projected AND converted this frame, by the fused epilogue —
    # n_selected·M conversions pinned to the emitted payload rows
    events = power_mod.frontend_frame_events(
        float(fe_cfg.image_h * fe_cfg.image_w),
        fe_cfg.patch.pixels_per_patch, fe_cfg.patch.n_vectors,
        n_selected_patches=n_selected, n_converted_patches=n_selected,
    )
    received = jnp.where(sel.valid, received, 0.0)
    b = jnp.arange(received.shape[0])[:, None]
    saliency = jnp.zeros(
        (received.shape[0], fe_cfg.n_patches), jnp.float32
    ).at[b, sel.indices].max(received)
    aux = {
        "indices": sel.indices, "valid": sel.valid,
        "saliency": saliency, "energy": sel.energy, "events": events,
    }
    return logits, aux


def vit_forward_compact(
    params: dict,
    rgb: jnp.ndarray,
    cfg: ViTConfig,
    indices: jnp.ndarray | None = None,
    mask: jnp.ndarray | None = None,
    project_fn=None,
    precomputed=None,
    cache=None,
    wire: str | None = None,
    k_cap: jnp.ndarray | None = None,
    stale_cap: jnp.ndarray | None = None,
    sign_mode: jnp.ndarray | None = None,
    backend_cache=None,
    backend_eps: jnp.ndarray | None = None,
    backend_act: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, dict]:
    """Compact path: frontend projects only the k selected patches, the
    backend attends over exactly those k tokens (index-looked-up positional
    embeddings), and the attention itself scores the next saccade.

    On the analog path the frontend hands over the digital wire format —
    int8 ADC codes plus static dequant metadata (DESIGN.md §9) — and the
    first matmul (:func:`_embed_tokens`) is the only place it is
    dequantized. ``wire="float"`` selects the bit-identical STE float
    view instead (differentiable: compact-path co-design training);
    ``None`` defers to the frontend's per-config resolution (codes iff
    there is a real edge ADC).

    ``precomputed`` optionally forwards an existing ``(patches, weights)``
    pair from :func:`repro.core.frontend.sensor_patches` (the serving
    engine computes it once for its in-step bootstrap).

    ``cache`` (a :class:`repro.core.temporal.FeatureCache`) enables the
    temporal delta gate: only the stale subset of the selection is
    re-projected/converted, held codes serve the rest (DESIGN.md §6).

    ``k_cap`` / ``stale_cap`` are the power governor's per-stream data
    knobs (DESIGN.md §10), forwarded to the frontend: shed tokens past
    ``k_cap`` (they leave attention via the valid mask) and truncate the
    temporal recompute allocation to ``stale_cap`` slots. Data, not
    shape — governed and ungoverned steps share one compilation.

    ``sign_mode`` ((B,) bool) is the governor's ADC-less tier knob
    (DESIGN.md §13): flagged rows have their served int8 code wire
    degraded to its 1-bit sign view (static code-grid points from
    :func:`repro.core.adc.sign_code_points`) and this frame's ADC
    conversions re-ledgered as sign comparisons. Data only — the payload
    stays int8 and no shape changes, so governed readout switches never
    retrace; the refreshed cache keeps the REAL codes (the comparator
    readout is non-destructive), so a recovering slot resumes from
    full-precision held charge. Requires the code wire.

    Returns (logits (B, n_classes), aux) with aux:
      ``indices`` (B, k)  — the patches that were ADC-converted;
      ``valid``   (B, k)  — False only on filler slots (< k active);
      ``events``          — this frame's executed energy-event ledger
        (:class:`repro.core.power.EventCounts`, (B,) leaves): what the
        frontend actually spent — price with ``EnergyMeter`` (§10);
      ``saliency``(B, P)  — backend attention scattered back onto the patch
        grid (unobserved patches score 0): frame t+1's selection signal;
      ``energy``  (B, P)  — the in-pixel patch-energy proxy (free from the
        frontend; the saccade explore term reads it here instead of
        re-running ``sensor_patches``);
      with ``cache`` given, additionally ``cache`` (the refreshed
      FeatureCache to thread into the next frame) and ``n_stale`` (B,)
      — how many of the k patches were actually recomputed.

    ``backend_cache`` (a :class:`repro.models.backend_delta.BackendCache`)
    enables the delta-gated incremental BACKEND (DESIGN.md §14): tokens
    whose served wire row is bitwise unchanged reuse their cached
    per-layer activations, a frame with no changed valid row serves the
    cached logits/saliency outright, and ``backend_eps`` ((B,) float,
    default exact) budgets deeper-layer reuse — ``eps <= 0`` reproduces
    the dense backend bitwise; ``eps > 0`` snaps sub-eps drift back to
    the cache. The executed backend MACs land on
    ``aux["events"].backend_macs`` and the refreshed cache on
    ``aux["backend_cache"]``. ``backend_act`` ((B,) bool) optionally
    restricts the whole-batch skip predicate to the slots that actually
    advance this frame (the engine's ``active & fed``) — a held or
    empty slot must not force a compute frame on an otherwise fully
    cached fleet.

    With ``cfg.fused_embed`` (requires ``quant_embed`` + analog frontend,
    code wire, no cache/project_fn) the whole frontend-to-embed seam runs
    as ONE Pallas megakernel with ragged per-slot k (DESIGN.md §11) —
    same logits, bitwise, for the same selection.
    """
    if backend_cache is None and (backend_eps is not None
                                  or backend_act is not None):
        raise ValueError(
            "backend_eps/backend_act configure the delta-gated backend "
            "(DESIGN.md §14) and need a BackendCache to gate against — "
            "pass backend_cache, or drop them for the dense encoder")
    if cfg.fused_embed:
        if backend_cache is not None:
            raise ValueError(
                "fused_embed does not thread the backend cache (the "
                "embed seam lives in-kernel, DESIGN.md §11); use "
                "fused_embed=False for the delta-gated backend")
        if sign_mode is not None:
            raise ValueError(
                "fused_embed consumes codes in-kernel (DESIGN.md §11); "
                "the sign-tier degradation needs the staged code wire — "
                "use fused_embed=False in a sign-tier governed engine")
        return _forward_compact_fused(
            params, rgb, cfg, indices, mask, project_fn, precomputed,
            cache, wire, k_cap, stale_cap,
        )
    # layer scopes (metadata only; DESIGN.md §15)
    with jax.named_scope("frontend"):
        out = apply_frontend(
            params["ip2"], rgb, cfg.frontend,
            mask=mask, indices=indices, mode="compact", project_fn=project_fn,
            precomputed=precomputed, cache=cache, wire=wire,
            k_cap=k_cap, stale_cap=stale_cap,
        )
        new_cache = None
        if cache is not None:
            out, new_cache = out
        cf: CompactFeatures = out
        if sign_mode is not None:
            if jnp.issubdtype(cf.features.dtype, jnp.floating):
                raise ValueError(
                    "sign_mode degrades the int8 code wire (DESIGN.md §13); "
                    "the float wire has no codes to degrade — it is the STE "
                    "training view, not a served payload")
            c_thresh, c_pos, c_neg = adc_mod.sign_code_points(
                cfg.frontend.patch.summer.v_ref, cfg.frontend.adc)
            sm = sign_mode[:, None, None]
            cf = cf._replace(features=jnp.where(
                sm,
                jnp.where(cf.features >= c_thresh, c_pos, c_neg)
                   .astype(cf.features.dtype),
                cf.features))
            ev = cf.events
            cf = cf._replace(events=ev._replace(
                adc_conversions=jnp.where(sign_mode, 0.0, ev.adc_conversions),
                sign_comparisons=jnp.where(
                    sign_mode, ev.adc_conversions, ev.sign_comparisons),
            ))
    with jax.named_scope("encoder"):
        new_bcache = None
        backend_macs = None
        if backend_cache is not None:
            from repro.models import backend_delta  # lazy: it imports us back

            if backend_cache.feats.dtype != cf.features.dtype:
                raise ValueError(
                    f"backend cache dtype {backend_cache.feats.dtype} does "
                    f"not match wire payload {cf.features.dtype}; build it "
                    f"with init_backend_cache(..., dtype=<wire dtype>)")
            if backend_cache.feats.shape[-2:] != cf.features.shape[-2:]:
                raise ValueError(
                    f"backend cache rows {backend_cache.feats.shape[-2:]} do "
                    f"not match the served wire {cf.features.shape[-2:]}")
            eps = (jnp.zeros(cf.valid.shape[0], jnp.float32)
                   if backend_eps is None
                   else jnp.broadcast_to(
                       jnp.asarray(backend_eps, jnp.float32),
                       (cf.valid.shape[0],)))

            def embed_fn(cf=cf):
                # index-based positional embeddings: pos[idx], not pos over P
                with jax.named_scope("embed"):
                    return (_embed_tokens(params, cf, cfg)
                            + params["pos"][cf.indices])

            logits, received, new_bcache, backend_macs = \
                backend_delta.delta_forward(params, cfg, cf, embed_fn,
                                            backend_cache, eps,
                                            act=backend_act)
        else:
            # index-based positional embeddings: pos[idx], not pos over P
            with jax.named_scope("embed"):
                x = _embed_tokens(params, cf, cfg) + params["pos"][cf.indices]
            logits, received = _encoder(params, x, cfg, cf.valid)

        received = jnp.where(cf.valid, received, 0.0)
        b = jnp.arange(received.shape[0])[:, None]
        saliency = jnp.zeros(
            (received.shape[0], cfg.frontend.n_patches), jnp.float32
        ).at[b, cf.indices].max(received)
    events = cf.events
    if backend_macs is not None:
        # the ledger prices the delta accelerator's EXECUTED MACs (§14);
        # the dense path deliberately ledgers none — its closed form is
        # dense_backend_macs, the governor's feed-forward estimate
        events = events._replace(backend_macs=backend_macs)
    aux = {
        "indices": cf.indices, "valid": cf.valid,
        "saliency": saliency, "energy": cf.energy, "events": events,
    }
    if new_cache is not None:
        aux["cache"] = new_cache
        aux["n_stale"] = new_cache.n_stale
    if new_bcache is not None:
        aux["backend_cache"] = new_bcache
    return logits, aux


def vit_loss(params, rgb, labels, cfg: ViTConfig):
    logits = vit_forward(params, rgb, cfg)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    loss = jnp.mean(logz - gold)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, acc
