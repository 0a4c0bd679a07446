"""Paper-core unit tests: PWM/DAC quantizers, switched-cap physics,
projection, Bayer/AA, saliency, ADC, QTH attention, power/throughput."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as c
from repro.core.switched_cap import SummerSpec, TAU_LEAK_65NM_S, TAU_LEAK_22NM_FDX_S


KEY = jax.random.PRNGKey(0)


class TestPWM:
    def test_levels(self):
        spec = c.QuantSpec(pwm_bits=6)
        x = jnp.linspace(0, 1, 1000)
        q = c.pwm_quantize(x, spec)
        assert len(np.unique(np.asarray(q))) == 64

    def test_clipping(self):
        q = c.pwm_quantize(jnp.array([-0.5, 1.5]))
        assert q[0] == 0.0 and q[1] == 1.0

    def test_ste_gradient_identity(self):
        g = jax.grad(lambda x: c.pwm_quantize(x).sum())(jnp.array([0.3, 0.7]))
        np.testing.assert_allclose(g, 1.0)

    def test_weight_quantization_signed(self):
        w = jax.random.normal(KEY, (8, 64))
        wq, scale = c.quantize_weights(w, c.QuantSpec(weight_bits=6))
        codes = np.asarray(jnp.round(wq / scale))
        assert np.abs(codes).max() <= 31  # 6-bit signed DAC
        # quantization error bounded by half an LSB
        assert float(jnp.abs(wq - w).max()) <= float(scale.max()) * 0.5 + 1e-6


class TestSwitchedCap:
    def test_paper_leakage_datum(self):
        """§2.1.2: passive summer of 768@1V + 768@0V droops ~10% in 10µs."""
        v = jnp.concatenate([jnp.ones(768), jnp.zeros(768)])
        passive = c.charge_share_sum(v, SummerSpec(mode="passive"))
        np.testing.assert_allclose(float(passive), 0.45, atol=1e-3)  # 0.5 * 0.9

    def test_opamp_compensation(self):
        v = jnp.concatenate([jnp.ones(768), jnp.zeros(768)])
        active = c.charge_share_sum(v, SummerSpec(mode="opamp"))
        assert abs(float(active) - 0.5) < 1e-3  # gain error only

    def test_tau_calibration(self):
        assert math.isclose(math.exp(-10e-6 / TAU_LEAK_65NM_S), 0.9, rel_tol=1e-9)
        assert TAU_LEAK_22NM_FDX_S == pytest.approx(100 * TAU_LEAK_65NM_S)

    def test_droop_trace_monotone(self):
        t = jnp.linspace(0, 50e-6, 10)
        tr = c.passive_droop_trace(jnp.array(1.0), t)
        assert bool(jnp.all(jnp.diff(tr) < 0))

    def test_capacitor_divider(self):
        assert float(c.capacitor_divider(jnp.array(1.0), 3)) == pytest.approx(0.25)

    def test_charge_conservation_mean(self):
        v = jax.random.uniform(KEY, (100,))
        s = c.charge_share_sum(v, SummerSpec(mode="opamp", opamp_dc_gain=1e12))
        np.testing.assert_allclose(float(s), float(v.mean()), rtol=1e-6)


class TestProjection:
    def test_matches_ideal_at_high_bits(self):
        """With many bits + ideal summer the analog path -> exact matmul/N²."""
        spec = c.PatchSpec(
            patch_h=8, patch_w=8, n_vectors=16,
            quant=c.QuantSpec(pwm_bits=16, weight_bits=16),
            summer=SummerSpec(opamp_dc_gain=1e12),
        )
        patches = jax.random.uniform(KEY, (5, 64))
        w = jax.random.normal(jax.random.PRNGKey(1), (16, 64))
        out = c.analog_project_patches(patches, w, spec)
        ref = patches @ w.T / 64
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-3)

    def test_programmable_patch_sizes(self):
        for ph, pw in [(8, 8), (8, 32), (24, 16), (32, 32)]:
            spec = c.PatchSpec(patch_h=ph, patch_w=pw, n_vectors=4)
            frame = jax.random.uniform(KEY, (96, 96))
            out = c.analog_project_frame(frame, jnp.ones((4, ph * pw)), spec)
            assert out.shape == ((96 // ph) * (96 // pw), 4)

    def test_invalid_patch_size_raises(self):
        with pytest.raises(ValueError):
            c.PatchSpec(patch_h=12, patch_w=8)

    def test_extract_patches_layout(self):
        frame = jnp.arange(16.0).reshape(4, 4)
        p = c.extract_patches(frame, 2, 2)
        np.testing.assert_allclose(np.asarray(p[0]), [0, 1, 4, 5])


class TestBayer:
    def test_mosaic_rggb(self):
        rgb = jnp.stack([jnp.full((4, 4), 0.1), jnp.full((4, 4), 0.5),
                         jnp.full((4, 4), 0.9)], axis=-1)
        m = c.mosaic(rgb)
        assert float(m[0, 0]) == pytest.approx(0.1)  # R
        assert float(m[0, 1]) == pytest.approx(0.5)  # G
        assert float(m[1, 0]) == pytest.approx(0.5)  # G
        assert float(m[1, 1]) == pytest.approx(0.9)  # B

    def test_strike_columns_identity(self):
        """A' applied to Bayer frame == A applied to RGB masked to Bayer."""
        a = jax.random.normal(KEY, (6, 8 * 8 * 3))
        ap = c.strike_columns(a, 8, 8)
        assert ap.shape == (6, 64)
        rgb = jax.random.uniform(jax.random.PRNGKey(2), (8, 8, 3))
        bayer_vec = c.mosaic(rgb).reshape(-1)
        ch = np.asarray(c.bayer_channel_map(8, 8)).reshape(-1)
        rgb_vec = rgb.reshape(-1, 3)
        manual = sum(
            float(a[0, i * 3 + ch[i]]) * float(rgb_vec[i, ch[i]]) for i in range(64)
        ) if False else None
        # A'(bayer) must equal selecting matched columns of A
        a3 = a.reshape(6, 64, 3)
        expected = jnp.einsum(
            "mv,v->m", a3[:, jnp.arange(64), ch], bayer_vec
        )
        got = ap @ bayer_vec
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=1e-5)

    def test_antialias_dc_preserving(self):
        x = jnp.full((16, 16), 0.7)
        y = c.antialias(x, 0.25)
        np.testing.assert_allclose(np.asarray(y), 0.7, rtol=1e-5)

    def test_antialias_cutoff_order(self):
        """0.25-Nyquist filter removes more high-freq energy than 0.5."""
        x = jnp.asarray(np.indices((32, 32)).sum(0) % 2, jnp.float32)  # checker
        hf = lambda z: float(jnp.var(z))
        assert hf(c.antialias(x, 0.25)) < hf(c.antialias(x, 0.5)) < hf(x)


def _onehot_mosaic(rgb):
    """The one-hot contraction the mosaic was once written as."""
    h, w = rgb.shape[-3], rgb.shape[-2]
    onehot = jax.nn.one_hot(c.bayer_channel_map(h, w), 3, dtype=rgb.dtype)
    return jnp.einsum("...hwc,hwc->...hw", rgb, onehot)


def _oracle_sensor_patches(rgb, cutoff, patch):
    """The sensor as once written, kept as the oracle: a tap-window stack
    contracted by einsum, a transpose for the H pass, the three channels
    restacked, the one-hot mosaic, then extract_patches."""
    k = c.bayer.gaussian_kernel_1d(cutoff)
    r = (k.shape[0] - 1) // 2

    def conv_last(x):
        xp = jnp.concatenate(
            [x[..., 1 : r + 1][..., ::-1], x, x[..., -r - 1 : -1][..., ::-1]],
            axis=-1)
        windows = jnp.stack([xp[..., i : i + x.shape[-1]]
                             for i in range(2 * r + 1)], axis=-1)
        return jnp.einsum("...k,k->...", windows, k)

    def old_antialias(x):
        out = conv_last(x)
        return conv_last(out.swapaxes(-1, -2)).swapaxes(-1, -2)

    blurred = jnp.stack([old_antialias(rgb[..., ch]) for ch in range(3)],
                        axis=-1)
    return c.extract_patches(_onehot_mosaic(blurred), patch, patch)


class TestSensorOracle:
    @pytest.mark.parametrize("case,cutoff,hw", [
        ("patches", 0.5, (64, 64)),
        ("patches", 0.25, (64, 64)),
        ("patches", 0.5, (64, 128)),
        ("patches", 0.25, (64, 128)),
        ("mosaic", None, (64, 128)),
    ])
    def test_matches_oracle(self, case, cutoff, hw):
        """sensor_patches (shifted float32 adds, parity select) against the
        stack/einsum/one-hot form; the mosaic selection is bitwise equal."""
        h, w = hw
        rgb = jax.random.uniform(jax.random.PRNGKey(h + w), (3, h, w, 3))
        with jax.default_matmul_precision("float32"):
            if case == "mosaic":
                np.testing.assert_array_equal(
                    np.asarray(c.mosaic(rgb)), np.asarray(_onehot_mosaic(rgb)))
                return
            want = _oracle_sensor_patches(rgb, cutoff, 16)
            cfg = c.FrontendConfig(
                image_h=h, image_w=w, aa_cutoff=cutoff,
                patch=c.PatchSpec(patch_h=16, patch_w=16, n_vectors=8))
            params = c.init_frontend_params(KEY, cfg)
            got, weights = c.sensor_patches(params, rgb, cfg)
        assert got.shape == want.shape == (3, (h // 16) * (w // 16), 256)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=2e-6)
        np.testing.assert_array_equal(
            np.asarray(weights),
            np.asarray(c.strike_columns(params["a_rgb"], 16, 16)))


class TestSaliencyADC:
    def test_topk_fraction(self):
        scores = jax.random.uniform(KEY, (3, 64))
        mask = c.topk_patch_mask(scores, 0.25)
        np.testing.assert_allclose(np.asarray(mask.sum(-1)), 16)

    def test_topk_mask_tied_scores_exactly_k(self):
        """Regression: equal scores must never over-select. The old
        ``scores >= thresh`` comparison returned every tied patch, breaking
        compact_active's exactly-k contract."""
        scores = jnp.ones((2, 16))                       # all tied
        mask = c.topk_patch_mask(scores, 0.25)
        np.testing.assert_allclose(np.asarray(mask.sum(-1)), 4)
        # deterministic tie-break: lowest patch indices win
        assert bool(mask[:, :4].all()) and not bool(mask[:, 4:].any())
        # partial tie at the threshold value
        scores = jnp.array([[0.9, 0.5, 0.5, 0.5, 0.5, 0.1, 0.0, 0.0]])
        mask = c.topk_patch_mask(scores, 0.25)           # k = 2
        np.testing.assert_allclose(np.asarray(mask), [[True, True] + [False] * 6])

    def test_topk_indices_deterministic_and_sorted_by_score(self):
        scores = jnp.array([[0.1, 0.7, 0.7, 0.9, 0.0]])
        idx = c.topk_patch_indices(scores, 3)
        np.testing.assert_array_equal(np.asarray(idx), [[3, 1, 2]])

    def test_mask_index_roundtrip(self):
        scores = jax.random.uniform(KEY, (4, 32))
        idx = c.topk_patch_indices(scores, 8)
        mask = c.mask_from_indices(idx, 32)
        np.testing.assert_allclose(np.asarray(mask.sum(-1)), 8)
        idx2, valid = c.indices_from_mask(mask, 8)
        assert bool(valid.all())
        np.testing.assert_array_equal(
            np.sort(np.asarray(idx), -1), np.asarray(idx2)   # ascending order
        )

    def test_indices_from_mask_fewer_than_k(self):
        mask = jnp.zeros((1, 8), bool).at[0, 2].set(True).at[0, 6].set(True)
        idx, valid = c.indices_from_mask(mask, 4)
        np.testing.assert_array_equal(np.asarray(idx[0, :2]), [2, 6])
        np.testing.assert_array_equal(np.asarray(valid), [[True, True, False, False]])

    def test_compact_active_exactly_k_on_ties(self):
        feats = jax.random.normal(KEY, (2, 16, 4))
        mask = c.topk_patch_mask(jnp.ones((2, 16)), 0.25)
        compact, idx = c.compact_active(feats, mask, 4)
        assert compact.shape == (2, 4, 4) and idx.shape == (2, 4)
        np.testing.assert_allclose(
            np.asarray(compact), np.asarray(feats[:, :4])    # ties -> lowest idx
        )

    def test_adc_levels(self):
        spec = c.ADCSpec(bits=8)
        x = jnp.linspace(-1, 1, 3000)
        q = c.adc_quantize(x, spec)
        assert len(np.unique(np.asarray(q))) == 256

    def test_digital_readout_recovers_bias(self):
        spec = c.ADCSpec(bits=14)
        out_v = jnp.array([0.3])
        got = c.digital_readout(out_v, v_ref=0.1, bias=0.05, spec=spec)
        np.testing.assert_allclose(float(got[0]), 0.3 - 0.1 + 0.05, atol=1e-3)


class TestQTH:
    def test_pow2_values(self):
        p = jnp.array([0.5, 0.25, 0.1, 1e-6])
        q = c.pow2_quantize(p, c.QTHSpec(min_exp=-8, ste=False))
        assert float(q[0]) == 0.5 and float(q[1]) == 0.25
        assert float(q[3]) == 0.0  # thresholded
        assert math.log2(float(q[2])) == round(math.log2(float(q[2])))

    def test_qth_attention_close_to_softmax(self):
        q = jax.random.normal(KEY, (2, 8, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 16))
        exact = jax.nn.softmax(
            jnp.einsum("bqd,bkd->bqk", q, k) / 4.0, -1
        ) @ v
        approx = c.qth_attention(q, k, v)
        rel = float(jnp.abs(approx - exact).max() / jnp.abs(exact).max())
        assert rel < 0.35  # pow-2 coefficients approximate softmax


class TestPowerThroughput:
    def test_table1_totals(self):
        t = c.AreaBudget().totals()
        assert t["Total"]["total_um2"] == pytest.approx(485.0)
        assert t["Total"]["pitch_um"] == pytest.approx(22.0, abs=0.05)
        assert t["Cap 30 fF"]["occupancy"] == pytest.approx(0.40, abs=0.005)

    def test_power_claims(self):
        rep = c.power_report(c.SensorConfig())            # 2 Mpix @ 30 Hz
        assert rep.total_w < 0.060                        # < 60 mW
        assert rep.mw_per_mpix < 30.0                     # < 30 mW/Mpix
        assert rep.adc_dominated                          # ADC is the majority

    def test_data_reduction_10x_30x(self):
        assert c.data_reduction(c.SensorConfig()) >= 10.0
        assert c.data_reduction(c.SensorConfig(), vs_rgb=True) >= 30.0

    def test_fig3_operating_points(self):
        p = c.rate_point("1080p", 2, 32, 400)
        assert 85.0 <= p.frame_hz <= 95.0                  # ~90 Hz claim
        assert c.frame_rate(8, 192, 2) > 30.0              # 8x8/192vec > 30 Hz

    def test_fig3_monotone_in_weight_lines(self):
        rates = [c.rate_point("1080p", cl, 32, 400).frame_hz for cl in (1, 2, 4, 8)]
        assert rates == sorted(rates)
