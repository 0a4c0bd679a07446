"""What running on a chip rests on: the device peaks table and the
roofline terms priced against it, the compile-cache placement, and the
registered ip2-vit widths the on-chip smoke serves."""

import json
import pathlib

import jax
import pytest

from repro import compile_cache
from repro.configs.registry import get_config
from repro.models.vit import vit_config_from
from repro.roofline.analysis import RooflineTerms, megakernel_cost
from repro.roofline.peaks import PEAKS, V5E, peaks_for

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_v5e_peaks_are_the_published_values():
    # Google Cloud documentation, "TPU v5e"
    p = peaks_for("TPU v5 lite")
    assert p is V5E
    assert p.bf16_flops == 197e12
    assert p.int8_ops == 393e12
    assert p.hbm_bytes_per_s == 819e9
    assert p.hbm_bytes == 16 * 1024**3
    assert p.ici_bytes_per_s * 8 * 4 == 1600e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5e", ""])
def test_unknown_device_kind_raises(kind):
    assert kind not in PEAKS
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for(kind)


def test_int8_work_is_priced_at_the_int8_peak():
    flops = 2.0e12
    bf16 = RooflineTerms(flops, 0.0, 0.0)
    int8 = RooflineTerms(flops, 0.0, 0.0, int8_flops_per_chip=flops)
    assert bf16.t_compute == pytest.approx(flops / 197e12)
    assert int8.t_compute == pytest.approx(flops / 393e12)
    half = RooflineTerms(flops, 0.0, 0.0, int8_flops_per_chip=flops / 2)
    assert half.t_compute == pytest.approx(
        flops / 2 / 197e12 + flops / 2 / 393e12)


def test_megakernel_cost_splits_out_its_int8_embed():
    proj = megakernel_cost([16] * 4, 16, 1024, 192)
    fused = megakernel_cost([16] * 4, 16, 1024, 192, d=256)
    assert proj["int8_flops"] == 0.0
    assert fused["int8_flops"] == fused["flops"] - proj["flops"] > 0
    t = RooflineTerms(fused["flops"], fused["bytes"], 0.0,
                      int8_flops_per_chip=fused["int8_flops"])
    assert t.t_compute < RooflineTerms(
        fused["flops"], fused["bytes"], 0.0).t_compute


def test_stored_roofline_sweep_prices_the_fused_embed_at_int8():
    """The block sweep in BENCH_throughput.json carries the embed's int8
    share, and its compute term is priced with it."""
    art = json.loads((ROOT / "BENCH_throughput.json").read_text())
    sweep = [r["roofline"]["model"] for r in art["roofline(§11)"]
             if "model" in r.get("roofline", {})]
    assert sweep
    for m in sweep:
        int8 = m["int8_flops_per_chip"]
        assert 0 < int8 < m["flops_per_chip"]
        assert m["t_compute_s"] == pytest.approx(
            (m["flops_per_chip"] - int8) / V5E.bf16_flops
            + int8 / V5E.int8_ops, rel=1e-12)
        assert m["t_memory_s"] == pytest.approx(
            m["bytes_per_chip"] / V5E.hbm_bytes_per_s, rel=1e-12)


def test_vit_config_from_registered_ip2_vit_widths():
    cfg = vit_config_from(get_config("ip2-vit"), quant_embed=True)
    fc = cfg.frontend
    assert (fc.image_h, fc.image_w) == (256, 256)
    assert (fc.patch.patch_h, fc.patch.n_vectors) == (32, 192)
    assert (fc.n_patches, fc.n_active) == (64, 16)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_ff) == (
        6, 256, 4, 1024)
    assert cfg.quant_embed
    with pytest.raises(ValueError, match="IP2"):
        vit_config_from(get_config("llama3-8b"))


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_the_fixed_repo_dir(
        monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.REPO_CACHE_DIR)
    assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.REPO_CACHE_DIR.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
