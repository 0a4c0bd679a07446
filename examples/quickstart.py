"""Quickstart: one frame through the IP2 in-pixel analog front-end.

    PYTHONPATH=src python examples/quickstart.py

Shows: scene -> AA optics -> Bayer -> salient patch selection -> analog
PWM/switched-cap projection (6-bit) -> edge ADC -> compact feature stream,
plus the sensor's power/area/throughput report (paper Table 1 / Fig. 3).
"""

import jax
import jax.numpy as jnp

import repro.core as c
from repro.data.pipeline import SceneStream
from repro.kernels import ops
from repro.compile_cache import enable_compile_cache


def main():
    # --- configure the sensor (the paper's 32x32/400-vector design scaled
    # to a 128px demo frame with 16x16 patches) ---
    fcfg = c.FrontendConfig(
        image_h=128, image_w=128,
        patch=c.PatchSpec(patch_h=16, patch_w=16, n_vectors=48),
        active_fraction=0.25, aa_cutoff=0.5,
    )
    params = c.init_frontend_params(jax.random.PRNGKey(0), fcfg)

    rgb, labels = SceneStream(image=128).batch(0, 2)
    rgb = jnp.asarray(rgb)

    feats, mask = c.apply_frontend(params, rgb, fcfg)
    compact, idx = c.compact_features(feats, mask, fcfg)
    print(f"frame {rgb.shape} -> {fcfg.n_patches} patches, "
          f"{int(mask[0].sum())} active ({fcfg.active_fraction:.0%})")
    print(f"features: {feats.shape} -> compact ADC stream {compact.shape}")
    n_in = rgb[0].size
    n_out = compact[0].size
    print(f"data reduction this frame: {n_in / n_out:.1f}x vs RGB")

    # the same projection through the Pallas TPU kernel (interpret on CPU)
    patches = c.extract_patches(c.mosaic(rgb), 16, 16)
    w = c.strike_columns(params["a_rgb"], 16, 16)
    k_out = ops.ip2_project(patches, w, fcfg.patch)
    ref = c.analog_project_patches(patches, w, fcfg.patch)
    print(f"pallas kernel vs analog reference max |diff|: "
          f"{float(jnp.abs(k_out - ref).max()):.2e}")

    # --- sensor-level reports (paper Table 1, §2.1.3, Fig. 3) ---
    rep = c.power_report(c.SensorConfig())
    print(f"\n2Mpix@30Hz front-end power: {rep.total_w * 1e3:.1f} mW "
          f"({rep.mw_per_mpix:.1f} mW/Mpix, ADC share "
          f"{rep.share()['adc']:.0%})")
    p = c.rate_point("1080p", 2, 32, 400)
    print(f"1080p, C=2 weight lines, 400 vec/32x32 patch: {p.frame_hz:.0f} Hz")
    area = c.AreaBudget().totals()
    print(f"in-pixel circuit: {area['Total']['total_um2']:.0f} um^2 -> "
          f"{area['Total']['pitch_um']:.1f} um pitch (65nm)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
