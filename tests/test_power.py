"""core/power.py invariants (DESIGN.md §10): the event-metered energy
subsystem.

Three contracts:

* **Meter == closed form.** ``power_report`` is DEFINED as the
  :class:`EnergyMeter` evaluated on the analytical steady-state event
  counts; asserting exact equality here pins that construction so a
  future "optimization" cannot split the two views apart.
* **Physical monotonicity + the paper's claims.** Front-end power is
  monotone in active fraction, frame rate and vectors/patch, and the ADC
  stays the majority consumer across the paper's operating envelope.
* **Runtime emission.** The events ``apply_frontend`` reports are the
  events it executed: k·M conversions on the ungated compact path,
  n_stale·M under the temporal gate, identical across wire formats and
  kernel adapters (the fused-ADC epilogue's count is the wrapper's
  ``frame_conversions``), and exactly the analytical counts at a matched
  operating point.

Hypothesis drives the adversarial sweeps where available; a
deterministic battery keeps every contract exercised on a bare-jax
container (mirroring tests/test_saliency_properties.py).
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

from repro.core.frontend import FrontendConfig, apply_frontend, init_frontend_params
from repro.core.power import (
    EnergyConstants,
    EnergyMeter,
    EventCounts,
    PowerReport,
    SensorConfig,
    frontend_frame_events,
    power_report,
    steady_state_events,
)
from repro.core.projection import PatchSpec
from repro.core.temporal import TemporalSpec, init_feature_cache
from repro.kernels import ops

KEY = jax.random.PRNGKey(0)

# the paper's operating envelope (§2.1.3/§2.1.4): 32x32 patches, >=192
# vectors (the 8x8 point uses 192), a meaningful saccade gate, video rates
PAPER_SWEEP = [
    SensorConfig(n_pixels=x, frame_hz=r, n_vectors=m, active_fraction=f)
    for x in (1.0e6, 2.0e6, 4.0e6)
    for r in (15.0, 30.0, 60.0, 90.0)
    for m in (192, 400, 768)
    for f in (0.2, 0.25, 0.35, 0.5)
]


def _fcfg(**kw):
    base = dict(
        image_h=256, image_w=256,
        patch=PatchSpec(patch_h=16, patch_w=16, n_vectors=400),
        aa_cutoff=None, active_fraction=0.25,
    )
    base.update(kw)
    return FrontendConfig(**base)


# --------------------------------------------------------------------------
# meter == closed form, and report structure
# --------------------------------------------------------------------------

class TestMeterEqualsClosedForm:
    def test_exact_equality_at_paper_point(self):
        rep = power_report(SensorConfig())
        bd = EnergyMeter().power_w(
            steady_state_events(SensorConfig()), SensorConfig().frame_hz)
        assert rep.components == bd.components      # exact, every component
        assert rep.total_w == bd.total_w

    def test_exact_equality_across_sweep(self):
        for cfg in PAPER_SWEEP[:: 7]:
            rep = power_report(cfg)
            bd = EnergyMeter().power_w(steady_state_events(cfg), cfg.frame_hz)
            assert rep.components == bd.components, cfg
            assert rep.total_w == bd.total_w == sum(bd.components.values())

    def test_report_structure_separates_components_and_totals(self):
        """Satellite of PR 5: no name-filtering — components is pure
        component watts, totals live in their own fields."""
        rep = power_report(SensorConfig())
        assert isinstance(rep, PowerReport)
        assert set(rep.components) == {
            "adc", "weight_dac", "cap_charging", "pwm_comparators",
            "opamps", "cds_sampling", "pixel_dump",
            "sign_comparators", "weight_reprogram", "backend",
        }
        assert rep.total_w == sum(rep.components.values())
        assert rep.share()["adc"] == rep.components["adc"] / rep.total_w
        assert sum(rep.share().values()) == pytest.approx(1.0)
        assert rep.dominant in rep.components

    def test_mw_per_mpix_claim_held(self):
        rep = power_report(SensorConfig())
        assert rep.mw_per_mpix < 30.0
        assert power_report(SensorConfig(n_pixels=1e6)).mw_per_mpix < 30.0


class TestPhysicalMonotonicity:
    def _total(self, **kw):
        return power_report(SensorConfig(**kw)).total_w

    def test_monotone_in_active_fraction(self):
        ts = [self._total(active_fraction=f) for f in (0.1, 0.25, 0.5, 1.0)]
        assert ts == sorted(ts) and ts[-1] > ts[0]

    def test_monotone_in_frame_rate(self):
        ts = [self._total(frame_hz=r) for r in (15.0, 30.0, 60.0, 120.0)]
        assert ts == sorted(ts) and ts[-1] > ts[0]

    def test_monotone_in_vectors(self):
        ts = [self._total(n_vectors=m) for m in (100, 192, 400, 768)]
        assert ts == sorted(ts) and ts[-1] > ts[0]

    def test_adc_majority_across_paper_sweep(self):
        for cfg in PAPER_SWEEP:
            rep = power_report(cfg)
            assert rep.adc_dominated, (cfg, rep.components)

    def test_event_counts_arithmetic(self):
        a = EventCounts(adc_conversions=3.0, cds_samples=4.0)
        b = EventCounts(adc_conversions=1.0, pixel_dumps=2.0)
        s = a.add(b)
        assert s.adc_conversions == 4.0 and s.cds_samples == 4.0
        assert s.pixel_dumps == 2.0
        assert a.scale(2.0).adc_conversions == 6.0
        assert EventCounts.zeros().adc_conversions == 0.0


if HAVE_HYPOTHESIS:

    class TestMonotonicityHypothesis:
        @given(
            f=st.floats(0.05, 0.95),
            bump=st.floats(1.05, 4.0),
            r=st.floats(5.0, 100.0),
            m=st.integers(16, 768),
        )
        @settings(max_examples=40, deadline=None)
        def test_more_activity_rate_or_vectors_never_cheaper(self, f, bump, r, m):
            base = SensorConfig(active_fraction=f, frame_hz=r, n_vectors=m)
            t0 = power_report(base).total_w
            assert power_report(
                dataclasses.replace(base, active_fraction=min(1.0, f * bump))
            ).total_w >= t0
            assert power_report(
                dataclasses.replace(base, frame_hz=r * bump)).total_w > t0
            # at least one vector more: int(16 * 1.05) is still 16
            more = max(m + 1, int(m * bump))
            assert power_report(
                dataclasses.replace(base, n_vectors=more)).total_w > t0

        @given(
            f=st.floats(0.05, 1.0),
            r=st.floats(5.0, 100.0),
            m=st.integers(16, 768),
            x=st.floats(0.25e6, 8e6),
        )
        @settings(max_examples=40, deadline=None)
        def test_meter_equals_closed_form_everywhere(self, f, r, m, x):
            cfg = SensorConfig(
                n_pixels=x, frame_hz=r, n_vectors=m, active_fraction=f)
            rep = power_report(cfg)
            bd = EnergyMeter().power_w(steady_state_events(cfg), r)
            assert rep.components == bd.components
            assert rep.total_w == bd.total_w


# --------------------------------------------------------------------------
# runtime emission: the ledger reports what was executed
# --------------------------------------------------------------------------

class TestRuntimeEmission:
    def test_compact_ungated_counts(self):
        cfg = _fcfg()
        params = init_frontend_params(KEY, cfg)
        rgb = jax.random.uniform(KEY, (2, 256, 256, 3))
        cf = apply_frontend(params, rgb, cfg, mode="compact")
        k, n2, m = cfg.n_active, cfg.patch.pixels_per_patch, cfg.patch.n_vectors
        x = 256 * 256
        ev = jax.tree.map(np.asarray, cf.events)
        assert ev.adc_conversions.shape == (2,)
        np.testing.assert_array_equal(ev.adc_conversions, k * m)
        np.testing.assert_array_equal(ev.cap_charges, k * n2 * m)
        np.testing.assert_array_equal(ev.dac_loads, m * n2)
        np.testing.assert_array_equal(ev.cds_samples, 2 * x)
        np.testing.assert_array_equal(ev.pixel_dumps, x - k * n2)
        np.testing.assert_array_equal(ev.pwm_pixel_frames, k * n2)
        np.testing.assert_array_equal(ev.opamp_patch_frames, k)

    def test_events_identical_across_wire_formats(self):
        cfg = _fcfg()
        params = init_frontend_params(KEY, cfg)
        rgb = jax.random.uniform(KEY, (1, 256, 256, 3))
        ev_c = apply_frontend(params, rgb, cfg, mode="compact", wire="codes").events
        ev_f = apply_frontend(params, rgb, cfg, mode="compact", wire="float").events
        for a, b in zip(ev_c, ev_f):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_measured_equals_analytical_at_matched_point(self):
        """A real frontend run at the paper's 25 % operating geometry
        (32x32 patches, 400 vectors) must report EXACTLY the analytical
        steady-state counts — the measured-vs-claimed bridge of DESIGN.md
        §10. The <30 mW/MP normalization itself only amortizes at
        megapixel scale (the DAC broadcast is a fixed M·N² cost per
        frame, regardless of sensor size); the bench measures it on a
        true 2 MP run, here we pin count equality and the per-MP match."""
        cfg = _fcfg(patch=PatchSpec(patch_h=32, patch_w=32, n_vectors=400))
        params = init_frontend_params(KEY, cfg)      # 256², P=64, k=16
        rgb = jax.random.uniform(KEY, (1, 256, 256, 3))
        cf = apply_frontend(params, rgb, cfg, mode="compact")
        scfg = SensorConfig(n_pixels=float(256 * 256), n_vectors=400,
                            active_fraction=0.25)
        analytical = steady_state_events(scfg)
        for name, a, b in zip(EventCounts._fields, cf.events, analytical):
            assert float(np.asarray(a)[0]) == float(b), name
        mw = EnergyMeter().power_mw(
            jax.tree.map(lambda e: float(np.asarray(e)[0]), cf.events), 30.0)
        rep = power_report(scfg)
        assert mw / (scfg.n_pixels / 1e6) == pytest.approx(
            rep.mw_per_mpix, rel=1e-6)
        # the claim at the paper's own sensor scale, same geometry
        assert power_report(SensorConfig()).mw_per_mpix < 30.0

    def test_temporal_counts_track_n_stale(self):
        cfg = _fcfg(
            image_h=64, image_w=64,
            patch=PatchSpec(patch_h=16, patch_w=16, n_vectors=32),
            temporal=TemporalSpec(delta_threshold=1e-4),
        )
        params = init_frontend_params(KEY, cfg)
        rgb = jax.random.uniform(KEY, (2, 64, 64, 3))
        m = cfg.patch.n_vectors
        cache = init_feature_cache(cfg, (2,))
        for t in range(4):
            cf, cache = apply_frontend(params, rgb, cfg, mode="compact",
                                       cache=cache)
            np.testing.assert_array_equal(
                np.asarray(cf.events.adc_conversions),
                np.asarray(cache.n_stale) * m,
            )
        # static scene: steady-state holds are free — zero conversions
        assert int(np.asarray(cache.n_stale).sum()) == 0
        assert float(np.asarray(cf.events.adc_conversions).sum()) == 0.0
        # but the per-frame fixed costs never disappear
        assert float(np.asarray(cf.events.cds_samples).min()) == 2.0 * 64 * 64

    def test_kernel_adapter_counts_match_fused_epilogue(self):
        """The wrapper's advertised conversion count is the emitted
        payload — M per REAL row, MXU padding never priced."""
        cfg = _fcfg(image_h=64, image_w=64,
                    patch=PatchSpec(patch_h=16, patch_w=16, n_vectors=32),
                    active_fraction=0.2)     # k=3: forces block_r padding
        params = init_frontend_params(KEY, cfg)
        rgb = jax.random.uniform(KEY, (1, 64, 64, 3))
        k, m = cfg.n_active, cfg.patch.n_vectors
        assert k == 3
        fn = ops.ip2_codes_fn(cfg.patch, cfg.adc)
        cf = apply_frontend(params, rgb, cfg, mode="compact", project_fn=fn)
        assert fn.frame_conversions(k) == k * m
        assert cf.features.size == 1 * k * m == fn.frame_conversions(k)
        assert float(np.asarray(cf.events.adc_conversions)[0]) == k * m
        # the no-fused-ADC adapter converts nothing itself
        assert ops.ip2_project_fn(cfg.patch).frame_conversions(k) == 0
        assert ops.fused_adc_conversions(k, cfg.patch, cfg.adc) == k * m

    def test_k_cap_sheds_conversions_and_dumps_patches(self):
        cfg = _fcfg(image_h=64, image_w=64,
                    patch=PatchSpec(patch_h=16, patch_w=16, n_vectors=32))
        params = init_frontend_params(KEY, cfg)
        rgb = jax.random.uniform(KEY, (2, 64, 64, 3))
        k, n2, m = cfg.n_active, cfg.patch.pixels_per_patch, cfg.patch.n_vectors
        cap = jnp.asarray([2, k], jnp.int32)
        cf = apply_frontend(params, rgb, cfg, mode="compact", k_cap=cap)
        np.testing.assert_array_equal(
            np.asarray(cf.events.adc_conversions), [2 * m, k * m])
        np.testing.assert_array_equal(
            np.asarray(cf.events.pixel_dumps),
            [64 * 64 - 2 * n2, 64 * 64 - k * n2])
        # shed tokens are invalid and served as zero
        v = np.asarray(cf.valid)
        assert v[0].sum() == 2 and v[1].sum() == k
        np.testing.assert_array_equal(np.asarray(cf.gain)[0, 2:], 0.0)
        # k_cap = k is a bitwise no-op
        base = apply_frontend(params, rgb, cfg, mode="compact")
        full = apply_frontend(params, rgb, cfg, mode="compact",
                              k_cap=jnp.asarray([k, k], jnp.int32))
        np.testing.assert_array_equal(np.asarray(base.features),
                                      np.asarray(full.features))
        np.testing.assert_array_equal(np.asarray(base.valid),
                                      np.asarray(full.valid))

    def test_stale_cap_truncates_recompute(self):
        cfg = _fcfg(image_h=64, image_w=64,
                    patch=PatchSpec(patch_h=16, patch_w=16, n_vectors=32),
                    temporal=TemporalSpec(delta_threshold=1e-4))
        params = init_frontend_params(KEY, cfg)
        k, m = cfg.n_active, cfg.patch.n_vectors
        cache = init_feature_cache(cfg, (1,))
        rgbs = jax.random.uniform(KEY, (3, 1, 64, 64, 3))
        cap = jnp.asarray([2], jnp.int32)
        for t in range(3):                      # full motion: all stale
            cf, cache = apply_frontend(params, rgbs[t], cfg, mode="compact",
                                       cache=cache, stale_cap=cap)
            assert int(np.asarray(cache.n_stale)[0]) <= 2
            assert float(np.asarray(cf.events.adc_conversions)[0]) <= 2 * m
        # without the cap the full-motion demand is the whole selection
        cf2, cache2 = apply_frontend(params, rgbs[0], cfg, mode="compact",
                                     cache=init_feature_cache(cfg, (1,)))
        assert int(np.asarray(cache2.n_stale)[0]) == k

    def test_governor_knobs_require_compact_or_cache(self):
        cfg = _fcfg(image_h=64, image_w=64,
                    patch=PatchSpec(patch_h=16, patch_w=16, n_vectors=32))
        params = init_frontend_params(KEY, cfg)
        rgb = jax.random.uniform(KEY, (1, 64, 64, 3))
        with pytest.raises(ValueError, match="compact"):
            apply_frontend(params, rgb, cfg, mode="dense",
                           k_cap=jnp.asarray([1], jnp.int32))
        with pytest.raises(ValueError, match="FeatureCache"):
            apply_frontend(params, rgb, cfg, mode="compact",
                           stale_cap=jnp.asarray([1], jnp.int32))
        # k_cap sheds TRAILING slots: a mask-derived selection is in
        # ascending patch order, not saliency order — refused, not
        # silently mis-shed
        mask = jnp.zeros((1, cfg.n_patches), bool).at[:, :cfg.n_active].set(True)
        with pytest.raises(ValueError, match="ranked"):
            apply_frontend(params, rgb, cfg, mode="compact", mask=mask,
                           k_cap=jnp.asarray([1], jnp.int32))

    def test_custom_constants_reprice_without_reserving(self):
        """Counts are constants-free: one emitted ledger prices under any
        calibration (recalibration never touches device state)."""
        cfg = _fcfg()
        params = init_frontend_params(KEY, cfg)
        rgb = jax.random.uniform(KEY, (1, 256, 256, 3))
        ev = jax.tree.map(
            lambda e: float(np.asarray(e)[0]),
            apply_frontend(params, rgb, cfg, mode="compact").events)
        cheap = EnergyMeter(EnergyConstants(e_adc_j=1.0e-9))
        dear = EnergyMeter(EnergyConstants(e_adc_j=8.0e-9))
        assert dear.power_mw(ev, 30.0) > EnergyMeter().power_mw(ev, 30.0) \
            > cheap.power_mw(ev, 30.0)
