"""Property-based tests for the analog primitives (`core/switched_cap.py`,
`core/adc.py`): charge-share linearity and scale invariance, passive droop
monotone decay and its consistency with `SummerSpec.droop_factor`, and the
ADC encode->decode round-trip within 1 LSB.

Same pattern as `test_saliency_properties.py`: each invariant is a plain
checker; hypothesis drives them with adversarial inputs when installed
(requirements-dev), and a seeded deterministic battery always runs so the
physics invariants stay covered even without hypothesis (e.g. a bare-jax
container)."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.adc import ADCSpec, adc_quantize, digital_readout
from repro.core.analog_nl import AnalogNLSpec, analog_nonlinearity
from repro.core.switched_cap import (
    SummerSpec,
    TAU_LEAK_65NM_S,
    charge_share_sum,
    passive_droop_trace,
)

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# invariant checkers (shared by the hypothesis and deterministic drivers)
# ---------------------------------------------------------------------------

def check_charge_share_linearity(
    x: np.ndarray, y: np.ndarray, a: float, b: float, spec: SummerSpec
) -> None:
    """Charge conservation makes the summer linear in the charges:
    f(a*x + b*y) - V_R == a*(f(x) - V_R) + b*(f(y) - V_R)."""
    f = lambda v: np.asarray(charge_share_sum(jnp.asarray(v), spec))
    lhs = f(a * x + b * y) - spec.v_ref
    rhs = a * (f(x) - spec.v_ref) + b * (f(y) - spec.v_ref)
    scale = max(1.0, np.abs(lhs).max(), np.abs(rhs).max())
    np.testing.assert_allclose(lhs, rhs, atol=1e-5 * scale)


def check_charge_share_is_scaled_mean(x: np.ndarray, spec: SummerSpec) -> None:
    """The summing node settles at V_R + droop * mean(charges): the 1/N²
    factor is physics (total capacitance N²·C), not a design choice."""
    out = np.asarray(charge_share_sum(jnp.asarray(x), spec))
    want = spec.v_ref + spec.droop_factor() * x.mean(axis=-1)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def check_droop_trace_monotone_and_calibrated(
    v0: float, times_us: np.ndarray
) -> None:
    """V(t) = v0 * exp(-t/tau): strictly monotone toward 0, and the 65 nm
    calibration point (10 % loss at 10 µs) falls on the curve."""
    t = np.sort(times_us) * 1e-6
    v = np.asarray(passive_droop_trace(jnp.float32(v0), jnp.asarray(t)))
    dv = np.diff(v)
    if v0 > 0:
        assert (dv <= 1e-7).all(), "positive hold voltage must decay"
    elif v0 < 0:
        assert (dv >= -1e-7).all(), "negative hold voltage must rise to 0"
    assert (np.abs(v) <= abs(v0) + 1e-7).all()
    v10 = float(passive_droop_trace(jnp.float32(v0), jnp.asarray([10e-6]))[0])
    np.testing.assert_allclose(v10, 0.9 * v0, rtol=1e-5, atol=1e-7)


def check_droop_factor_matches_trace(hold_us: float) -> None:
    """SummerSpec(mode='passive').droop_factor() must equal the trace's
    retention at hold_time for the same tau — one leakage model, two
    entry points."""
    spec = SummerSpec(mode="passive", hold_time_s=hold_us * 1e-6,
                      tau_leak_s=TAU_LEAK_65NM_S)
    trace = float(passive_droop_trace(jnp.float32(1.0),
                                      jnp.asarray([hold_us * 1e-6]))[0])
    np.testing.assert_allclose(spec.droop_factor(), trace, rtol=1e-6)


def check_adc_roundtrip_within_1_lsb(v: np.ndarray, bits: int) -> None:
    """encode->decode: inside the rails the code recovers the voltage to
    within LSB/2 (mid-rise quantizer); outside it clips to the rails. The
    full digital_readout additionally recovers sigma(W·P)/N² + b from
    Out_v = V_R + sigma within 1 LSB."""
    spec = ADCSpec(bits=bits)
    lsb = (spec.v_max - spec.v_min) / (spec.levels - 1)
    q = np.asarray(adc_quantize(jnp.asarray(v), spec))
    clipped = np.clip(v, spec.v_min, spec.v_max)
    assert (np.abs(q - clipped) <= lsb / 2 + 1e-7).all()
    # codes land on the grid (atol in code units: f32 voltage rounding is
    # ~1e-7/lsb codes, far below the 0.5 that would mean a wrong code)
    codes = (q - spec.v_min) / lsb
    np.testing.assert_allclose(codes, np.round(codes), atol=5e-3)

    v_ref, bias = 0.25, 0.03125
    sigma = clipped - v_ref                     # representable signal range
    dig = np.asarray(digital_readout(
        jnp.asarray(sigma + v_ref), v_ref, bias, spec))
    assert (np.abs(dig - (sigma + bias)) <= lsb / 2 + 1e-7).all()


# ---------------------------------------------------------------------------
# hypothesis drivers (adversarial inputs; skipped without hypothesis)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    charges = st.integers(1, 64).flatmap(
        lambda n: st.lists(
            st.floats(-1.0, 1.0, allow_nan=False, width=32),
            min_size=n, max_size=n,
        ).map(lambda v: np.asarray(v, np.float32))
    )
    summer_specs = st.sampled_from([
        SummerSpec(),
        SummerSpec(v_ref=0.5),
        SummerSpec(mode="passive"),
        SummerSpec(mode="passive", hold_time_s=1e-6),
        SummerSpec(opamp_dc_gain=100.0),
    ])

    class TestHypothesis:
        @settings(max_examples=50, deadline=None)
        @given(charges, st.floats(-2, 2, width=32), st.floats(-2, 2, width=32),
               summer_specs)
        def test_charge_share_linearity(self, x, a, b, spec):
            y = x[::-1].copy()
            check_charge_share_linearity(x, y, float(a), float(b), spec)

        @settings(max_examples=50, deadline=None)
        @given(charges, summer_specs)
        def test_charge_share_is_scaled_mean(self, x, spec):
            check_charge_share_is_scaled_mean(x, spec)

        @settings(max_examples=40, deadline=None)
        @given(st.floats(-1, 1, allow_nan=False, width=32),
               st.lists(st.floats(0, 100, allow_nan=False, width=32),
                        min_size=2, max_size=16))
        def test_droop_trace(self, v0, times_us):
            check_droop_trace_monotone_and_calibrated(
                float(v0), np.asarray(times_us, np.float64))

        @settings(max_examples=30, deadline=None)
        # a width-32 strategy takes only float32-exact bounds
        @given(st.floats(float(np.float32(0.01)), 100.0, width=32))
        def test_droop_factor_matches_trace(self, hold_us):
            check_droop_factor_matches_trace(float(hold_us))

        @settings(max_examples=50, deadline=None)
        @given(st.integers(2, 12),
               st.lists(st.floats(-2, 2, allow_nan=False, width=32),
                        min_size=1, max_size=32))
        def test_adc_roundtrip(self, bits, volts):
            check_adc_roundtrip_within_1_lsb(
                np.asarray(volts, np.float32), bits)


# ---------------------------------------------------------------------------
# deterministic battery (always runs)
# ---------------------------------------------------------------------------

_SPECS = [
    SummerSpec(),
    SummerSpec(v_ref=0.5),
    SummerSpec(mode="passive"),
    SummerSpec(mode="passive", hold_time_s=1e-6),
    SummerSpec(opamp_dc_gain=100.0),
]


@pytest.mark.parametrize("spec", _SPECS, ids=lambda s: f"{s.mode}-vr{s.v_ref:g}")
@pytest.mark.parametrize("seed", range(4))
def test_charge_share_battery(spec, seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 65))
    x = rng.uniform(-1, 1, size=n).astype(np.float32)
    y = rng.uniform(-1, 1, size=n).astype(np.float32)
    a, b = rng.uniform(-2, 2, size=2)
    check_charge_share_linearity(x, y, float(a), float(b), spec)
    check_charge_share_is_scaled_mean(x, spec)
    # batched: one patch per row, same physics
    check_charge_share_is_scaled_mean(
        rng.uniform(-1, 1, size=(3, n)).astype(np.float32), spec)


@pytest.mark.parametrize("v0", [1.0, 0.5, -0.5, 0.0, 1e-3])
def test_droop_trace_battery(v0):
    times = np.asarray([0.0, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0])
    check_droop_trace_monotone_and_calibrated(v0, times)


@pytest.mark.parametrize("hold_us", [0.1, 1.0, 5.0, 10.0, 40.0])
def test_droop_factor_trace_consistency_battery(hold_us):
    check_droop_factor_matches_trace(hold_us)


@pytest.mark.parametrize("bits", [2, 4, 6, 8, 10, 12])
def test_adc_roundtrip_battery(bits):
    rng = np.random.default_rng(bits)
    v = np.concatenate([
        rng.uniform(-2, 2, size=64),
        np.asarray([-2.0, -1.0, -0.5, 0.0, 0.5, 1.0, 2.0]),   # rails + clip
        np.linspace(-1, 1, 2 ** min(bits, 8)),                # on/near grid
    ]).astype(np.float32)
    check_adc_roundtrip_within_1_lsb(v, bits)


def test_opamp_droop_is_gain_error_not_leak():
    """OpAmp mode pins the summing node at virtual ground: retention is
    A0/(1+A0) regardless of hold time — the 'amplifiers can be removed in
    lower-leakage technology' trade the paper discusses."""
    for hold in (1e-6, 10e-6, 1e-3):
        spec = SummerSpec(mode="opamp", hold_time_s=hold)
        assert spec.droop_factor() == pytest.approx(10_000.0 / 10_001.0)
    # passive retention does depend on hold time
    r1 = SummerSpec(mode="passive", hold_time_s=1e-6).droop_factor()
    r2 = SummerSpec(mode="passive", hold_time_s=10e-6).droop_factor()
    assert r1 > r2


# ---------------------------------------------------------------------------
# 2T analog nonlinearity (core/analog_nl.py) — DESIGN.md §13 satellite
# ---------------------------------------------------------------------------

def check_nl_clip_bounds(v: np.ndarray, spec) -> None:
    """'none' clips to the ±v_sat rails, 'relu' rectifies to [0, v_sat] —
    the supply rail is a hard bound whatever the input."""
    out = np.asarray(analog_nonlinearity(jnp.asarray(v), spec))
    lo = -spec.v_sat if spec.kind == "none" else 0.0
    assert out.min() >= lo - 1e-7 and out.max() <= spec.v_sat + 1e-7
    # inside the rails the transfer is the identity; XLA flushes float32
    # subnormal inputs to zero, as it does on every backend
    inside = (v > lo) & (v < spec.v_sat)
    flushed = np.where(np.abs(v) < np.finfo(np.float32).tiny, 0.0, v)
    np.testing.assert_allclose(out[inside], flushed[inside], rtol=1e-6)


def check_nl_grad_finite(v: np.ndarray, spec) -> None:
    g = np.asarray(jax.vmap(jax.grad(
        lambda x: analog_nonlinearity(x, spec)))(jnp.asarray(v)))
    assert np.isfinite(g).all(), f"{spec.kind}: non-finite grad"


if HAVE_HYPOTHESIS:

    @settings(deadline=None, max_examples=50)
    @given(
        v=st.lists(st.floats(-500.0, 500.0, allow_nan=False), min_size=1,
                   max_size=32),
        kind=st.sampled_from(["none", "relu", "sigmoid"]),
    )
    def test_nl_bounded_and_differentiable_hypothesis(v, kind):
        arr = np.asarray(v, np.float32)
        spec = AnalogNLSpec(kind=kind)
        if kind != "sigmoid":
            check_nl_clip_bounds(arr, spec)
        out = np.asarray(analog_nonlinearity(jnp.asarray(arr), spec))
        assert np.isfinite(out).all()
        assert np.abs(out).max() <= spec.v_sat + 1e-7
        check_nl_grad_finite(arr, spec)


@pytest.mark.parametrize("kind", ["none", "relu"])
def test_nl_clip_battery(kind):
    rng = np.random.default_rng(7)
    v = np.concatenate([
        rng.uniform(-3, 3, 64),
        [-200.0, -1.0, -0.5, 0.0, 0.5, 1.0, 200.0],
    ]).astype(np.float32)
    check_nl_clip_bounds(v, AnalogNLSpec(kind=kind))
    check_nl_grad_finite(v, AnalogNLSpec(kind=kind))


def test_nl_sigmoid_shape():
    """The S-curve: strictly monotone, open range (0, v_sat), gain sets
    the slope at the bias point."""
    spec = AnalogNLSpec(kind="sigmoid", v_sat=0.8)
    # strict monotonicity holds where f32 can still resolve the slope
    # (past gain·v ≈ ±17 the output rounds onto the rails — that flat
    # tail is the saturation, not a monotonicity bug)
    v = jnp.linspace(-2.0, 2.0, 201)
    out = np.asarray(analog_nonlinearity(v, spec))
    assert (np.diff(out) > 0).all()
    assert out.min() > 0.0 and out.max() < spec.v_sat
    wide = np.asarray(analog_nonlinearity(jnp.linspace(-300.0, 300.0, 201),
                                          spec))
    assert (np.diff(wide) >= 0).all()
    assert wide.min() >= 0.0 and wide.max() <= spec.v_sat
    assert analog_nonlinearity(jnp.float32(0.0), spec) == pytest.approx(
        spec.v_sat / 2)
    # slope at 0 is gain·v_sat/4 (d/dv sigmoid(g v)·v_sat at v=0)
    g0 = float(jax.grad(lambda x: analog_nonlinearity(x, spec))(jnp.float32(0.0)))
    assert g0 == pytest.approx(spec.sigmoid_gain * spec.v_sat / 4, rel=1e-5)


def test_nl_sigmoid_saturated_inputs_regression():
    """Regression for the overflow bug: the naive v_sat/(1+exp(-g·v))
    form overflows exp() to inf at g·v <= -89 in f32 — value AND (via
    inf/inf) STE gradient went NaN. The stable form must return a finite,
    saturated value and an exactly-zero-or-finite gradient at ±200."""
    spec = AnalogNLSpec(kind="sigmoid")
    for v in (-200.0, 200.0):
        out = float(analog_nonlinearity(jnp.float32(v), spec))
        assert np.isfinite(out)
        g = float(jax.grad(
            lambda x: analog_nonlinearity(x, spec))(jnp.float32(v)))
        assert np.isfinite(g)
    assert float(analog_nonlinearity(jnp.float32(-200.0), spec)) == 0.0
    assert float(analog_nonlinearity(jnp.float32(200.0), spec)) \
        == pytest.approx(spec.v_sat)
    # the naive form is genuinely the bug being guarded against
    naive = 1.0 / (1.0 + np.exp(np.float32(200.0 * spec.sigmoid_gain)))
    assert naive == 0.0 or not np.isfinite(
        np.exp(np.float32(200.0 * spec.sigmoid_gain)))


def test_nl_unknown_kind_raises():
    with pytest.raises(ValueError, match="unknown analog nonlinearity"):
        analog_nonlinearity(jnp.zeros(()), AnalogNLSpec(kind="tanh"))
