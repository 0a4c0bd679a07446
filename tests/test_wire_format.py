"""Digital wire format (DESIGN.md §9): dtype discipline and round-trip
exactness of the ADC-code-native dataflow.

Two contracts:

* **Dtype discipline** — no float32 feature payload may leak into a wire
  or cache pytree leaf: ``CompactFeatures.features``,
  ``FeatureCache.features`` and the engine's ``StreamState.cache.features``
  must all stay at ADC code width (int8) through every mutation (step,
  admit wipe, evict, refresh). Scale/zero/gain metadata are O(M)/O(k)
  floats by design; the O(k·M) payload is the wire.

* **Round-trip exactness** — ``dequantize(digital_codes(v)) ==
  digital_readout(v)`` bit-for-bit for ANY v (the float view is defined
  as the dequant), and the affine inverts the encode exactly over the ADC
  grid. Property-driven under hypothesis, with an always-on deterministic
  battery so a bare-jax container still runs the checks.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

import repro.core as c
from repro.core import adc as adc_mod
from repro.core.frontend import FrontendConfig, apply_frontend, dequantize_features
from repro.core.projection import PatchSpec
from repro.core.temporal import TemporalSpec, init_feature_cache
from repro.data.pipeline import SceneStream
from repro.models.vit import ViTConfig, init_vit, vit_forward_compact
from repro.serve.engine import SaccadeEngine
from repro.serve.serve_step import make_bootstrap_indices, make_saccade_step

KEY = jax.random.PRNGKey(0)


def _fcfg(**kw):
    base = dict(
        image_h=64, image_w=64,
        patch=PatchSpec(patch_h=16, patch_w=16, n_vectors=32),
        active_fraction=0.25,
    )
    base.update(kw)
    return FrontendConfig(**base)


def _vcfg(fcfg, **kw):
    base = dict(frontend=fcfg, n_layers=1, d_model=32, n_heads=2, d_ff=64)
    base.update(kw)
    return ViTConfig(**base)


def _payload_leaves(tree):
    """Every pytree leaf that is a feature payload (a ``features`` field of
    CompactFeatures / FeatureCache, at any nesting depth)."""
    leaves = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        names = [getattr(p, "name", None) for p in path]
        if names and names[-1] == "features":
            leaves.append((jax.tree_util.keystr(path), leaf))
    return leaves


def _assert_code_payloads(tree, cfg):
    leaves = _payload_leaves(tree)
    assert leaves, "pytree carries no feature payload leaf"
    want = jnp.dtype(cfg.adc.code_dtype)
    for name, leaf in leaves:
        assert leaf.dtype == want, f"{name}: {leaf.dtype} leaked into the wire"
        assert leaf.nbytes == leaf.size * want.itemsize


class TestDtypeDiscipline:
    def test_apply_frontend_compact_payload_is_codes(self):
        fcfg = _fcfg()
        params = c.init_frontend_params(KEY, fcfg)
        rgb = jax.random.uniform(KEY, (2, 64, 64, 3))
        cf = apply_frontend(params, rgb, fcfg, mode="compact")
        _assert_code_payloads(cf, fcfg)
        # the wire payload is exactly k * M codes = k * M bytes at 8 bits
        assert cf.features.nbytes == 2 * fcfg.n_active * fcfg.patch.n_vectors

    def test_feature_cache_payload_is_codes(self):
        fcfg = _fcfg(temporal=TemporalSpec(delta_threshold=1e-5))
        params = c.init_frontend_params(KEY, fcfg)
        rgb = jax.random.uniform(KEY, (2, 64, 64, 3))
        cache = init_feature_cache(fcfg, (2,))
        _assert_code_payloads(cache, fcfg)
        for _ in range(3):
            cf, cache = apply_frontend(params, rgb, fcfg, mode="compact",
                                       cache=cache)
            _assert_code_payloads((cf, cache), fcfg)

    def test_stream_state_payload_stays_codes_under_churn(self):
        """step / admit (recycled slot) / evict never promote the held
        cache to float — the admit row wipe is the classic offender
        (where(hit, 0.0, int8) would silently upcast)."""
        fcfg = _fcfg(temporal=TemporalSpec(delta_threshold=1e-5))
        cfg = _vcfg(fcfg)
        params = init_vit(KEY, cfg)
        eng = SaccadeEngine(cfg, params, capacity=2, temporal=True)
        frame = SceneStream(image=64).batch(0, 1)[0][0]
        _assert_code_payloads(eng.state, fcfg)
        eng.admit("a")
        _assert_code_payloads(eng.state, fcfg)
        eng.step({"a": frame})
        _assert_code_payloads(eng.state, fcfg)
        eng.evict("a")
        eng.admit("b")          # recycled slot: full cache-row wipe
        _assert_code_payloads(eng.state, fcfg)
        eng.step({"b": frame})
        _assert_code_payloads(eng.state, fcfg)

    def test_saccade_step_aux_cache_is_codes(self):
        fcfg = _fcfg(temporal=TemporalSpec(delta_threshold=1e-5))
        cfg = _vcfg(fcfg)
        params = init_vit(KEY, cfg)
        rgb = jnp.asarray(SceneStream(image=64).batch(0, 2)[0])
        step = jax.jit(make_saccade_step(cfg, temporal=True))
        idx = make_bootstrap_indices(cfg)(params, rgb)
        cache = init_feature_cache(fcfg, (2,))
        _, _, _, cache = step(params, rgb, idx, cache)
        _assert_code_payloads(cache, fcfg)

    def test_cache_wire_mismatch_raises(self):
        fcfg = _fcfg(temporal=TemporalSpec(delta_threshold=1e-5))
        params = c.init_frontend_params(KEY, fcfg)
        rgb = jax.random.uniform(KEY, (1, 64, 64, 3))
        f32_cache = init_feature_cache(fcfg, (1,), dtype=jnp.float32)
        with pytest.raises(ValueError, match="does not match wire"):
            apply_frontend(params, rgb, fcfg, mode="compact", cache=f32_cache)
        code_cache = init_feature_cache(fcfg, (1,))
        with pytest.raises(ValueError, match="does not match wire"):
            apply_frontend(params, rgb, fcfg, mode="compact",
                           cache=code_cache, wire="float")

    def test_narrow_adc_still_int8_wide_adc_widens(self):
        assert jnp.dtype(adc_mod.ADCSpec(bits=4).code_dtype) == jnp.int8
        assert jnp.dtype(adc_mod.ADCSpec(bits=10).code_dtype) == jnp.int16

    def test_float_simulation_has_no_code_wire(self):
        """analog=False (the paper's algorithm simulation) has no edge
        ADC: the default wire resolves to the unquantized float view —
        keeping dense==compact equivalence exact for that config — and an
        explicit codes request raises."""
        fcfg = _fcfg(analog=False, bayer=False)
        params = c.init_frontend_params(KEY, fcfg)
        rgb = jax.random.uniform(KEY, (2, 64, 64, 3))
        dense, mask = apply_frontend(params, rgb, fcfg)
        cf = apply_frontend(params, rgb, fcfg, mask=mask, mode="compact")
        assert cf.features.dtype == jnp.float32
        gathered = jnp.take_along_axis(dense, cf.indices[..., None], axis=-2)
        np.testing.assert_array_equal(
            np.asarray(dequantize_features(cf)), np.asarray(gathered))
        with pytest.raises(ValueError, match="requires analog=True"):
            apply_frontend(params, rgb, fcfg, mode="compact", wire="codes")

    def test_codes_adapter_rejected_on_float_paths(self):
        """A codes-emitting kernel adapter must not be silently consumed
        as analog voltage by the dense or float-wire paths."""
        from repro.kernels import ops

        fcfg = _fcfg()
        params = c.init_frontend_params(KEY, fcfg)
        rgb = jax.random.uniform(KEY, (1, 64, 64, 3))
        codes_fn = ops.ip2_codes_fn(fcfg.patch, fcfg.adc, interpret=True)
        with pytest.raises(ValueError, match="emits wire-format codes"):
            apply_frontend(params, rgb, fcfg, mode="dense", project_fn=codes_fn)
        with pytest.raises(ValueError, match="emits wire-format codes"):
            apply_frontend(params, rgb, fcfg, mode="compact",
                           project_fn=codes_fn, wire="float")


def check_roundtrip_exact(v: np.ndarray, v_ref: float, bias, bits: int) -> None:
    """dequantize(digital_codes(v)) == digital_readout(v) BITWISE — the
    float path is defined as the dequant (DESIGN.md §9)."""
    spec = adc_mod.ADCSpec(bits=bits)
    va = jnp.asarray(v, jnp.float32)
    codes = adc_mod.digital_codes(va, v_ref, bias, spec)
    deq = adc_mod.dequantize(*codes)
    ro = adc_mod.digital_readout(va, v_ref, bias, spec)
    np.testing.assert_array_equal(np.asarray(deq), np.asarray(ro))
    # codes fit the advertised width and hit every voltage within lsb/2
    assert codes.codes.dtype == spec.code_dtype
    half_lsb = spec.lsb / 2 + 1e-7
    in_rails = (v >= spec.v_min) & (v <= spec.v_max)
    volts = np.asarray(deq) + np.asarray(
        jnp.asarray(v_ref - jnp.asarray(bias, jnp.float32))
    )
    assert np.abs(volts - v)[in_rails].max() <= half_lsb


def check_grid_identity(bits: int) -> None:
    """Over the exact ADC grid the conversion is the identity: every
    representable voltage encodes to itself (codes lose nothing that was
    ever on the wire — requant-free seams are exact)."""
    spec = adc_mod.ADCSpec(bits=bits)
    grid = spec.v_min + np.arange(spec.levels) * spec.lsb
    codes = adc_mod.encode(jnp.asarray(grid, jnp.float32), spec)
    assert len(np.unique(np.asarray(codes))) == spec.levels
    scale, zero = adc_mod.readout_scale_zero(0.0, 0.0, spec)
    back = np.asarray(adc_mod.dequantize(codes, scale, zero))
    np.testing.assert_allclose(back, grid, atol=spec.lsb * 1e-3)


class TestRoundTripDeterministic:
    """Always-on battery (runs without hypothesis)."""

    @pytest.mark.parametrize("bits", [4, 6, 8, 10])
    def test_grid_identity(self, bits):
        check_grid_identity(bits)

    @pytest.mark.parametrize("bits", [4, 8])
    def test_roundtrip_exact(self, bits):
        rng = np.random.default_rng(0)
        v = rng.uniform(-1.5, 1.5, size=257).astype(np.float32)
        bias = jnp.asarray(rng.normal(size=()) * 0.1, jnp.float32)
        check_roundtrip_exact(v, 0.3, bias, bits)

    def test_frontend_scale_zero_matches_adc(self):
        fcfg = _fcfg()
        params = c.init_frontend_params(KEY, fcfg)
        scale, zero = c.feature_scale_zero(params, fcfg)
        s2, z2 = adc_mod.readout_scale_zero(
            fcfg.patch.summer.v_ref, params["bias"], fcfg.adc)
        np.testing.assert_array_equal(np.asarray(scale), np.asarray(s2))
        np.testing.assert_array_equal(np.asarray(zero), np.asarray(z2))

    @pytest.mark.parametrize("traced_scale", [False, True])
    def test_dequantize_rounds_product_then_sum(self, traced_scale):
        """Every int8 code dequantizes to round(round(code * scale) +
        zero), also in a program that fuses the multiply into the add,
        where XLA:CPU would contract a plain affine into an FMA
        (DESIGN.md §9)."""
        codes = jnp.arange(-128, 128, dtype=jnp.int8)
        zero = (np.random.default_rng(0).normal(size=256) * 0.1
                ).astype(np.float32)
        scale = np.float32(2.0 / 255.0)
        want = (np.asarray(codes, np.float64) * scale).astype(np.float32) + zero
        if traced_scale:
            got = jax.jit(lambda s, z: adc_mod.dequantize(codes, s, z))(
                jnp.float32(scale), jnp.asarray(zero))
        else:
            got = jax.jit(lambda z: adc_mod.dequantize(codes, scale, z))(
                jnp.asarray(zero))
        np.testing.assert_array_equal(np.asarray(got), want)


if HAVE_HYPOTHESIS:

    @settings(max_examples=40, deadline=None)
    @given(
        bits=st.integers(2, 10),
        v_ref=st.floats(-0.5, 0.5),
        bias=st.floats(-0.2, 0.2),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_roundtrip_property(bits, v_ref, bias, seed):
        rng = np.random.default_rng(seed)
        v = rng.uniform(-2.0, 2.0, size=64).astype(np.float32)
        check_roundtrip_exact(v, v_ref, jnp.float32(bias), bits)

    @settings(max_examples=20, deadline=None)
    @given(bits=st.integers(2, 12))
    def test_grid_identity_property(bits):
        check_grid_identity(bits)


class TestSeamEquivalence:
    """The end-to-end obligation: the code path dequantizes to the float
    path exactly at every seam where no requant occurs."""

    def test_code_wire_equals_float_wire_bitwise(self):
        fcfg = _fcfg()
        params = c.init_frontend_params(KEY, fcfg)
        rgb = jax.random.uniform(KEY, (3, 64, 64, 3))
        cfc = apply_frontend(params, rgb, fcfg, mode="compact")
        cff = apply_frontend(params, rgb, fcfg, mode="compact", wire="float")
        assert cfc.features.dtype == jnp.int8
        assert cff.features.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(dequantize_features(cfc)),
            np.asarray(dequantize_features(cff)))

    def test_saccade_loop_logits_code_vs_float_wire(self):
        """Full closed-loop trajectory: logits AND selections from the
        code-native step equal the float-wire step bit for bit (same
        ADCSpec end to end — no requant anywhere)."""
        cfg = _vcfg(_fcfg(), n_layers=2, d_model=64, n_heads=4, d_ff=128)
        params = init_vit(KEY, cfg)
        stream = SceneStream(image=64)

        def make(wire):
            def step(p, rgb, idx):
                return vit_forward_compact(p, rgb, cfg, indices=idx, wire=wire)
            return jax.jit(step)

        s_code, s_float = make("codes"), make("float")
        idx = make_bootstrap_indices(cfg)(
            params, jnp.asarray(stream.batch(0, 3)[0]))
        for t in range(3):
            rgb = jnp.asarray(stream.batch(t, 3)[0])
            lc, auxc = s_code(params, rgb, idx)
            lf, auxf = s_float(params, rgb, idx)
            np.testing.assert_array_equal(np.asarray(lc), np.asarray(lf))
            np.testing.assert_array_equal(
                np.asarray(auxc["saliency"]), np.asarray(auxf["saliency"]))
            idx = c.topk_patch_indices(auxc["saliency"] + auxc["energy"] * 1e-3,
                                       cfg.frontend.n_active)

    def test_quant_embed_within_lsb_budget(self):
        """The w8a8 consumption path (codes straight into quant_matmul, no
        second activation rounding) stays within a couple of ADC LSBs of
        the exact dequant path — the weight-side int8 quantization is the
        only approximation."""
        fcfg = _fcfg()
        cfg = _vcfg(fcfg, n_layers=2, d_model=64, n_heads=4, d_ff=128)
        cfg_q = dataclasses.replace(cfg, quant_embed=True)
        params = init_vit(KEY, cfg)
        rgb = jax.random.uniform(jax.random.PRNGKey(5), (3, 64, 64, 3))
        exact, _ = vit_forward_compact(params, rgb, cfg)
        quant, _ = vit_forward_compact(params, rgb, cfg_q)
        lsb = fcfg.adc.lsb
        assert float(jnp.abs(exact - quant).max()) <= 2.0 * lsb
        # programmed-once weight prep (prepare_quant_embed) is bitwise the
        # same as the per-call fallback
        from repro.models.vit import prepare_quant_embed

        prepped, _ = vit_forward_compact(prepare_quant_embed(params), rgb, cfg_q)
        np.testing.assert_array_equal(np.asarray(prepped), np.asarray(quant))

    def test_changed_adcspec_requant_bounded_by_one_lsb(self):
        """The only seam allowed to move values: serving a cache written
        under one ADCSpec through a changed spec's dequant is a requant —
        bounded by one (coarser) LSB, exact when the spec is unchanged."""
        fcfg = _fcfg()
        params = c.init_frontend_params(KEY, fcfg)
        rgb = jax.random.uniform(KEY, (2, 64, 64, 3))
        cf = apply_frontend(params, rgb, fcfg, mode="compact")
        # same spec: exact (identity requant)
        re_enc = adc_mod.encode(
            dequantize_features(cf)
            + (fcfg.patch.summer.v_ref - params["bias"]), fcfg.adc)
        np.testing.assert_array_equal(np.asarray(re_enc), np.asarray(cf.features))
        # coarser spec: each value moves by at most half its (coarser) LSB
        coarse = adc_mod.ADCSpec(bits=6)
        volts = dequantize_features(cf) + (fcfg.patch.summer.v_ref - params["bias"])
        s, z = adc_mod.readout_scale_zero(fcfg.patch.summer.v_ref,
                                          params["bias"], coarse)
        requant = adc_mod.dequantize(adc_mod.encode(volts, coarse), s, z)
        err = jnp.abs(requant - dequantize_features(cf))
        assert float(err.max()) <= coarse.lsb


def _assert_sign_payloads(tree):
    """Sign-wire variant of :func:`_assert_code_payloads`: every feature
    payload leaf is the 1-bit comparator wire (bool, NOT int8 codes)."""
    leaves = _payload_leaves(tree)
    assert leaves, "pytree carries no feature payload leaf"
    for name, leaf in leaves:
        assert leaf.dtype == jnp.bool_, \
            f"{name}: {leaf.dtype} leaked into the sign wire"


class TestSignWireDtype:
    """DESIGN.md §13: wire='sign' is a third wire format with its own
    dtype discipline — the walks that pin the code wire pin it too."""

    def test_apply_frontend_sign_payload_is_bool(self):
        fcfg = _fcfg()
        params = c.init_frontend_params(KEY, fcfg)
        rgb = jax.random.uniform(KEY, (2, 64, 64, 3))
        cf = apply_frontend(params, rgb, fcfg, mode="compact", wire="sign")
        _assert_sign_payloads(cf)
        # metadata carries the sign affine, not the ADC affine
        scale, zero = adc_mod.sign_scale_zero(params["bias"])
        np.testing.assert_allclose(np.asarray(cf.scale),
                                   np.asarray(scale), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(cf.zero),
                                   np.asarray(zero), rtol=1e-6)

    def test_sign_cache_stays_bool_under_mutation(self):
        fcfg = _fcfg(temporal=TemporalSpec(delta_threshold=1e-5))
        params = c.init_frontend_params(KEY, fcfg)
        cache = init_feature_cache(fcfg, (2,), dtype=bool)
        _assert_sign_payloads(cache)
        for t in range(3):
            rgb = jax.random.uniform(jax.random.PRNGKey(t), (2, 64, 64, 3))
            cf, cache = apply_frontend(params, rgb, fcfg, mode="compact",
                                       wire="sign", cache=cache)
            _assert_sign_payloads((cf, cache))

    def test_sign_cache_wire_mismatch_raises_both_ways(self):
        fcfg = _fcfg(temporal=TemporalSpec(delta_threshold=1e-5))
        params = c.init_frontend_params(KEY, fcfg)
        rgb = jax.random.uniform(KEY, (2, 64, 64, 3))
        with pytest.raises(ValueError, match="does not match wire"):
            apply_frontend(params, rgb, fcfg, mode="compact", wire="sign",
                           cache=init_feature_cache(fcfg, (2,)))
        with pytest.raises(ValueError, match="does not match wire"):
            apply_frontend(params, rgb, fcfg, mode="compact", wire="codes",
                           cache=init_feature_cache(fcfg, (2,), dtype=bool))
        with pytest.raises(ValueError, match="does not match wire"):
            apply_frontend(params, rgb, fcfg, mode="compact", wire="float",
                           cache=init_feature_cache(fcfg, (2,), dtype=bool))


class TestBackendCacheDiscipline:
    """DESIGN.md §14: the BackendCache's reuse KEY rides the same wire
    format as the FeatureCache (int8 codes — the key is a bitwise
    comparison against served codes, so a float copy would both 4x the
    footprint and break exactness), while the activation payload
    ``x_out`` is deliberately float32 (it caches encoder outputs, not
    wire bytes). Every mutation — engine step, admit row-wipe, hold
    freeze — must preserve both dtypes, and the whole cache must stay a
    slot-major pytree (static shapes, shard/donate with the slot axis)."""

    def _beng(self, capacity=2):
        fcfg = _fcfg(temporal=TemporalSpec(delta_threshold=1e-5))
        cfg = _vcfg(fcfg)
        params = init_vit(KEY, cfg)
        eng = SaccadeEngine(cfg, params, capacity=capacity, temporal=True,
                            backend_delta=True)
        return cfg, eng

    def _assert_backend_cache(self, bc, cfg):
        want = jnp.dtype(cfg.frontend.adc.code_dtype)
        assert bc.feats.dtype == want, (
            f"backend reuse key left the wire: {bc.feats.dtype}")
        assert bc.x_out.dtype == jnp.float32
        assert bc.gain.dtype == jnp.float32
        assert bc.indices.dtype == jnp.int32
        assert bc.tvalid.dtype == jnp.bool_
        assert bc.valid.dtype == jnp.bool_

    def test_backend_cache_payload_stays_codes_under_churn(self):
        cfg, eng = self._beng()
        frame = SceneStream(image=64).batch(0, 1)[0][0]
        capacity = eng.capacity
        self._assert_backend_cache(eng.state.bcache, cfg)
        eng.admit("a")
        eng.step({"a": frame})
        self._assert_backend_cache(eng.state.bcache, cfg)
        eng.evict("a")
        eng.admit("b")              # recycled slot: full row wipe
        st = eng.state
        self._assert_backend_cache(st.bcache, cfg)
        assert not bool(st.bcache.valid[eng.slot_of("b")])
        eng.step({"b": frame})
        self._assert_backend_cache(eng.state.bcache, cfg)
        # slot-major discipline: every leaf keeps the static (S, ...) shape
        for leaf in jax.tree_util.tree_leaves(eng.state.bcache):
            assert leaf.shape[0] == capacity

    def test_backend_cache_wire_mismatch_raises_both_ways(self):
        from repro.models.backend_delta import init_backend_cache

        fcfg = _fcfg()
        cfg = _vcfg(fcfg)
        params = init_vit(KEY, cfg)
        rgb = jax.random.uniform(KEY, (1, 64, 64, 3))
        f32_bc = init_backend_cache(cfg, fcfg.n_active, (1,),
                                    dtype=jnp.float32)
        with pytest.raises(ValueError, match="does not match wire"):
            vit_forward_compact(params, rgb, cfg, backend_cache=f32_bc)
        code_bc = init_backend_cache(cfg, fcfg.n_active, (1,),
                                     dtype=fcfg.adc.code_dtype)
        with pytest.raises(ValueError, match="does not match wire"):
            vit_forward_compact(params, rgb, cfg, wire="float",
                                backend_cache=code_bc)

    def test_backend_cache_float_wire_pairs_with_float_key(self):
        """The float STE wire is a legal backend-delta pairing — the key
        comparison is still bitwise, just over f32 payloads."""
        from repro.models.backend_delta import init_backend_cache

        fcfg = _fcfg()
        cfg = _vcfg(fcfg)
        params = init_vit(KEY, cfg)
        rgb = jax.random.uniform(KEY, (1, 64, 64, 3))
        bc = init_backend_cache(cfg, fcfg.n_active, (1,), dtype=jnp.float32)
        logits, aux = vit_forward_compact(params, rgb, cfg, wire="float",
                                          backend_cache=bc)
        assert aux["backend_cache"].feats.dtype == jnp.float32
        logits2, aux2 = vit_forward_compact(
            params, rgb, cfg, wire="float",
            backend_cache=aux["backend_cache"])
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(logits2))
