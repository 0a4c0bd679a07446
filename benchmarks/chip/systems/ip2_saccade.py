"""The system under test of the ``ip2_saccade`` reference: the saccade
serving engine with the ViT classifier backend, built from a
configuration file, and the weights, which the benchmark makes itself
from the seed.

The program is imported only here, and only for what is measured: the
serving engine, its config dataclasses, the kernel adapter of the staged
code-wire path, the temporal-gate spec and the engine's telemetry. Beside
them this file gives what the shared harness must not assume of a
backend: the model operations of a frame (``frame_ops``, read by
``step_mfu``), the MACs of the head alone (``head_macs``), and the
comparison of a served output with the reference's (``NUMBERS``,
``compare``).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import model

# what the harness and the plain reference implement: a configuration
# that asks for anything else is refused, not run as something it is not
CONFIG_KEYS = {
    "frame_h", "frame_w", "n_vectors", "patch", "active_fraction",
    "n_layers", "d_model", "n_heads", "d_ff", "n_classes", "mlp", "norm",
    "norm_eps", "saliency_layers", "explore", "ema_decay",
    "aa_cutoff_nyquist", "bayer", "pwm_bits", "weight_bits", "adc_bits",
    "adc_v_min", "adc_v_max", "v_ref", "opamp_dc_gain", "analog_clip_v",
    "delta_threshold", "droop_lsb_budget", "matmul_precision", "serving",
    "init"}
# keys of this system that describe a configuration and change nothing
# that runs (beside the ones every system shares, ``model.CONFIG_NOTES``)
CONFIG_NOTES = {"embed_weights"}
FIXED = {"mlp": "gelu_tanh", "norm": "rms", "bayer": "RGGB",
         "saliency_layers": "last"}
# the one serving mode: the staged code-wire path, no governor
SERVING = {"quant_embed": True, "delta_kernel": True, "temporal": True,
           "backend_delta": True, "governor": None}
SERVING_NOTES = {"path"}
# the compared numbers, each with a limit in the cell's checks file
NUMBERS = ("logit_gap_max", "logit_gap_median", "gaze_gap_max")


def check_config(conf: dict) -> None:
    """Refuse a configuration with a key, a value or a serving mode that
    the harness and its reference do not implement."""
    extra = sorted(set(conf) - CONFIG_KEYS - CONFIG_NOTES
                   - model.CONFIG_NOTES)
    missing = sorted(CONFIG_KEYS - set(conf))
    sv = conf.get("serving", {})
    off = {k: conf[k] for k, v in FIXED.items() if conf.get(k) != v}
    off.update({f"serving.{k}": sv.get(k, "(missing)")
                for k, v in SERVING.items() if sv.get(k, "(missing)") != v})
    off.update({f"serving.{k}": sv[k]
                for k in set(sv) - set(SERVING) - SERVING_NOTES})
    if extra or missing or off:
        raise SystemExit(
            f"chipbench: configuration {conf.get('name')!r} asks for what "
            f"the harness does not implement: keys {extra}, missing "
            f"{missing}, values {off} (implemented: {FIXED}, serving "
            f"{SERVING})")


def make_weights(conf: dict, key) -> dict:
    """Random weights in the program's parameter layout, made on the
    device in one jitted call, in the types they are served in. The embed
    is served as int8 codes with one scale per output column (amax/127);
    ``embed`` holds that grid dequantized, which is what the reference
    embeds with."""
    p = conf["patch"]
    n2, m = p * p, conf["n_vectors"]
    d, h, f, c = conf["d_model"], conf["n_heads"], conf["d_ff"], conf["n_classes"]
    dh = d // h
    n_patches = (conf["frame_h"] // p) * (conf["frame_w"] // p)
    ini = conf["init"]

    def build(key):
        ks = iter(jax.random.split(key, 16 + 16 * conf["n_layers"]))
        nrm = lambda shape, std: jax.random.normal(next(ks), shape) * std
        dense = lambda shape, fan_in: nrm(shape, 1.0 / math.sqrt(fan_in))
        gain = lambda: 1.0 + nrm((d,), ini["norm_gain_std"])
        bias = lambda shape: nrm(shape, ini["bias_std"])
        embed = dense((m, d), m)
        s_w = jnp.maximum(jnp.max(jnp.abs(embed), axis=0), 1e-12) / 127.0
        w8 = jnp.clip(jnp.round(embed / s_w[None, :]), -127, 127).astype(
            jnp.int8)
        layers = []
        for _ in range(conf["n_layers"]):
            layers.append({
                "norm1": gain(),
                "attn": {"wq": dense((d, h, dh), d), "wk": dense((d, h, dh), d),
                         "wv": dense((d, h, dh), d), "wo": dense((h, dh, d), d),
                         "bq": bias((h, dh)), "bk": bias((h, dh)),
                         "bv": bias((h, dh))},
                "norm2": gain(),
                "mlp": {"w_up": dense((d, f), d), "b_up": bias((f,)),
                        "w_down": dense((f, d), f), "b_down": bias((d,))},
            })
        return {
            "ip2": {"a_rgb": nrm((m, 3 * n2),
                                 ini["a_rgb_std_per_sqrt_n2"] * math.sqrt(n2)),
                    "bias": bias((m,))},
            "embed": w8.astype(jnp.float32) * s_w[None, :],
            "embed_q": (w8, s_w.astype(jnp.float32)),
            "pos": nrm((n_patches, d), ini["pos_std"]),
            "layers": layers,
            "final_norm": gain(),
            "head": dense((d, c), d),
        }

    return jax.jit(build)(key)


def reference_weights(params: dict) -> dict:
    """The reference's view of the same benchmark-made arrays."""
    return {"a_rgb": params["ip2"]["a_rgb"], "bias": params["ip2"]["bias"],
            "embed": params["embed"], "pos": params["pos"],
            "layers": params["layers"], "final_norm": params["final_norm"],
            "head": params["head"]}


def vit_config(conf: dict):
    """The program's config for ``conf``, checked field by field against
    what the configuration file states."""
    from repro.core.temporal import TemporalSpec
    from repro.models.vit import ViTConfig

    base = ViTConfig()
    fc = base.frontend
    p = conf["patch"]
    fc = dataclasses.replace(
        fc, image_h=conf["frame_h"], image_w=conf["frame_w"],
        patch=dataclasses.replace(fc.patch, patch_h=p, patch_w=p,
                                  n_vectors=conf["n_vectors"]),
        active_fraction=conf["active_fraction"],
        aa_cutoff=conf["aa_cutoff_nyquist"],
        temporal=TemporalSpec(delta_threshold=conf["delta_threshold"],
                              droop_lsb_budget=conf["droop_lsb_budget"]))
    cfg = dataclasses.replace(
        base, frontend=fc, n_classes=conf["n_classes"],
        n_layers=conf["n_layers"], d_model=conf["d_model"],
        n_heads=conf["n_heads"], d_ff=conf["d_ff"], norm_eps=conf["norm_eps"],
        saliency_layers=conf["saliency_layers"],
        quant_embed=conf["serving"]["quant_embed"],
        delta_kernel=conf["serving"]["delta_kernel"])
    f = cfg.frontend
    stated = {
        "analog": (f.analog, True), "bayer": (f.bayer, True),
        "pwm_bits": (f.patch.quant.pwm_bits, conf["pwm_bits"]),
        "weight_bits": (f.patch.quant.weight_bits, conf["weight_bits"]),
        "adc_bits": (f.adc.bits, conf["adc_bits"]),
        "adc_v_min": (f.adc.v_min, conf["adc_v_min"]),
        "adc_v_max": (f.adc.v_max, conf["adc_v_max"]),
        "summer": (f.patch.summer.mode, "opamp"),
        "opamp_dc_gain": (f.patch.summer.opamp_dc_gain, conf["opamp_dc_gain"]),
        "v_ref": (f.patch.summer.v_ref, conf["v_ref"]),
        "analog_nl": (f.patch.nl.kind, "none"),
        "analog_clip_v": (f.patch.nl.v_sat, conf["analog_clip_v"]),
        "recompute_budget": (f.temporal.recompute_budget, None),
        "qth": (cfg.qth, False), "fused_embed": (cfg.fused_embed, False),
    }
    off = {k: v for k, v in stated.items() if v[0] != v[1]}
    if off:
        raise SystemExit(f"the program's config departs from the file: {off}")
    return cfg


def make_engine(conf: dict, params: dict, capacity: int, mesh=None):
    """The served engine: the staged code-wire path (ragged projection
    with the fused edge ADC, w8a8 embed, temporal gate, delta-gated
    backend with the delta-attention kernel), no governor; with a
    ``mesh`` (``model.slot_mesh``) its slots are sharded over its
    ``"data"`` axis."""
    from repro.kernels import ops
    from repro.serve.engine import SaccadeEngine

    cfg = vit_config(conf)
    fc = cfg.frontend
    sv = conf["serving"]
    # interpret=None compiles the kernels for the TPU the harness requires
    return SaccadeEngine(
        cfg, params, capacity=capacity, mesh=mesh, axis="data",
        project_fn=ops.ip2_codes_fn(fc.patch, fc.adc, interpret=None),
        temporal=sv["temporal"], backend_delta=sv["backend_delta"],
        explore=conf["explore"], ema_decay=conf["ema_decay"])


def telemetry():
    """The program's telemetry snapshot, or None where it has none (an
    older commit): what ``chipbench/engine_trace.py``'s readers read."""
    try:
        from repro.serve import telemetry as tel
    except ImportError:
        return None
    return tel.snapshot()


def backend_macs(n_tokens: int, n_layers: int, m: int, d: int, d_ff: int,
                 n_classes: int) -> float:
    """MACs of the dense backend on ``n_tokens`` tokens: embed, per layer
    Q/K/V, attention scores and mix over ``n_tokens`` keys, output
    projection and MLP, then the head."""
    per_layer = n_tokens * (3.0 * d * d) + n_tokens * (
        2.0 * n_tokens * d + d * d + 2.0 * d * d_ff)
    return n_tokens * m * d + n_layers * per_layer + n_classes * d


def head_macs(conf: dict) -> float:
    """MACs of the head alone: what a slot whose backend output was
    reused still computes."""
    return conf["n_classes"] * conf["d_model"]


def frame_ops(conf: dict, sizes: dict) -> dict:
    """Model operations of one served frame at full recompute, split by
    the precision they run at: the projection and the backend in float
    (priced at bf16), the embed in int8."""
    k = sizes["k"]
    n2 = conf["patch"] ** 2
    m, d = conf["n_vectors"], conf["d_model"]
    embed = 2.0 * k * m * d
    dense = 2.0 * backend_macs(k, conf["n_layers"], m, d, conf["d_ff"],
                               conf["n_classes"])
    return {"float": 2.0 * k * n2 * m + dense - embed, "int8": embed}


def compare(served, ref, gaps, mask) -> dict:
    """The compared numbers over the frames in ``mask``."""
    per_frame = np.max(np.abs(served - ref), axis=-1)[mask]
    finite = bool(np.all(np.isfinite(served[mask])))
    if not finite or per_frame.size == 0:
        return {"logit_gap_max": math.inf, "logit_gap_median": math.inf,
                "gaze_gap_max": math.inf, "frames": int(per_frame.size)}
    return {"logit_gap_max": float(np.max(per_frame)),
            "logit_gap_median": float(np.median(per_frame)),
            "gaze_gap_max": float(np.max(gaps[mask])),
            "frames": int(per_frame.size)}
