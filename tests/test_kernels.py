"""Per-kernel allclose vs the pure-jnp oracle (interpret=True on CPU),
swept over shapes/dtypes + hypothesis property tests (the property tests
are skipped when hypothesis is not installed; see requirements-dev.txt)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import adc as adc_mod
from repro.core import projection as proj
from repro.core.pwm import QuantSpec
from repro.kernels import ops, ref
from repro.kernels.ip2_project import IP2KernelParams, ip2_project_pallas

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("patch,n_vec,n_patches", [
    (8, 16, 5),          # min patch
    (16, 192, 12),       # mid, n_vec not mult of 128
    (32, 400, 3),        # paper's 32x32/400-vector operating point
    (32, 768, 1),        # paper's 768-vector point
])
def test_ip2_kernel_vs_core_reference(patch, n_vec, n_patches):
    spec = proj.PatchSpec(patch_h=patch, patch_w=patch, n_vectors=n_vec)
    patches = jax.random.uniform(KEY, (n_patches, patch * patch))
    w = jax.random.normal(jax.random.PRNGKey(1), (n_vec, patch * patch)) * 2.0
    out_k = ops.ip2_project(patches, w, spec, interpret=True)
    out_r = proj.analog_project_patches(patches, w, spec)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-5)


@pytest.mark.parametrize("pwm_bits,adc_bits,nl", [(6, 8, "none"), (4, 6, "relu"), (8, 10, "none")])
def test_ip2_kernel_quant_nl_adc_sweep(pwm_bits, adc_bits, nl):
    from repro.core.analog_nl import AnalogNLSpec

    spec = proj.PatchSpec(
        patch_h=8, patch_w=8, n_vectors=24,
        quant=QuantSpec(pwm_bits=pwm_bits),
        nl=AnalogNLSpec(kind=nl),
    )
    adc = adc_mod.ADCSpec(bits=adc_bits)
    patches = jax.random.uniform(KEY, (4, 7, 64))
    w = jax.random.normal(jax.random.PRNGKey(2), (24, 64)) * 3.0
    bias = jax.random.normal(jax.random.PRNGKey(3), (24,)) * 0.1
    out_k = ops.ip2_project(patches, w, spec, adc=adc, bias=bias, interpret=True)
    ref_analog = proj.analog_project_patches(patches, w, spec)
    out_r = adc_mod.digital_readout(ref_analog, spec.summer.v_ref, bias, adc)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-5)


def test_ip2_kernel_block_shape_sweep():
    """Different BlockSpec tilings must not change results."""
    spec = proj.PatchSpec(patch_h=16, patch_w=16, n_vectors=64)
    patches = jax.random.uniform(KEY, (40, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 256))
    base = ops.ip2_project(patches, w, spec, interpret=True)
    for bp, bm, bk in [(8, 128, 128), (128, 128, 512), (16, 256, 256)]:
        out = ops.ip2_project(
            patches, w, spec, block_p=bp, block_m=bm, block_k=bk, interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(base), atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(3, 100, 200), (1, 511, 130)])
def test_quant_matmul_vs_oracle(dtype, shape):
    b, k, m = shape
    a = (jax.random.normal(KEY, (b, k)) * 2).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, m))
    w8, sw = ops.quantize_weights_int8(w)
    got = ops.quant_matmul(a, w8, sw, interpret=True)
    a8, sa = ref.quantize_activations_ref(a.astype(jnp.float32).reshape(-1, k))
    want = ref.quant_matmul_ref(a8, sa, w8, sw).reshape(b, m).astype(dtype)
    # bf16 output rounding: lsb ≈ 0.8% of magnitude
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    np.testing.assert_allclose(
        np.asarray(got, np.float32) / scale, np.asarray(want, np.float32) / scale,
        atol=tol,
    )


def test_quant_matmul_pre_skips_second_rounding():
    """The pre-quantized entry consumes int8 codes + scales as-is (the
    ADC-code path, §9): no host re-quantization, oracle-exact, and the
    host-quantizing wrapper is exactly pre(quantize(a))."""
    a = jax.random.normal(KEY, (6, 40)) * 2
    a8, sa = ref.quantize_activations_ref(a)
    w = jax.random.normal(jax.random.PRNGKey(1), (40, 24))
    w8, sw = ops.quantize_weights_int8(w)
    got = ops.quant_matmul_pre(a8, sa, w8, sw, interpret=True)
    want = ref.quant_matmul_ref(a8, sa, w8, sw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    via_host = ops.quant_matmul(a, w8, sw, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(via_host), atol=1e-6)
    # scalar per-row scale broadcast (the ADC's single static LSB)
    got_s = ops.quant_matmul_pre(a8, jnp.float32(0.25), w8, sw, interpret=True)
    want_s = ref.quant_matmul_ref(a8, jnp.full((6,), 0.25, jnp.float32), w8, sw)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=1e-5)


def test_quant_matmul_accuracy_vs_float():
    a = jax.random.normal(KEY, (16, 300))
    w = jax.random.normal(jax.random.PRNGKey(1), (300, 200))
    w8, sw = ops.quantize_weights_int8(w)
    y = ops.quant_matmul(a, w8, sw, interpret=True)
    rel = float(jnp.abs(y - a @ w).max() / jnp.abs(a @ w).max())
    assert rel < 0.03


# ---------------------------------------------------------------------------
# sparse (active-patch-only) projection kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,cutoff,tb", [
    ((3, 64, 128, 3), 0.5, None),       # one band: reflection in-block
    ((3, 64, 128, 3), 0.25, None),
    ((2, 64, 512, 3), 0.5, 8),          # 8 bands of 8 rows, halo tiles
    ((2, 64, 512, 3), 0.25, 16),        # 4 bands of 16; edge lane tiles
    ((1, 28, 20, 3), 0.5, None),        # rows no multiple of 8
])
def test_bayer_frame_kernel_vs_oracle(shape, cutoff, tb):
    """The one-pass optics+mosaic kernel equals the AA filter then the
    mosaic, for every band split and at the frame's four edges."""
    from repro.core import bayer
    from repro.kernels.bayer_sensor import bayer_frame_pallas

    rgb = jax.random.uniform(KEY, shape)
    if tb is None:
        got = ops.bayer_frame(rgb, cutoff)
    else:
        got = bayer_frame_pallas(rgb, bayer.aa_taps(cutoff), bayer.RGGB,
                                 tb=tb, interpret=True)
    want = ref.bayer_frame_ref(rgb, cutoff)
    assert got.shape == shape[:-1] and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-6)


class TestSparseProjection:
    def _dense_gather(self, patches, w, idx, spec, **kw):
        dense = ops.ip2_project(patches, w, spec, interpret=True, **kw)
        return jnp.take_along_axis(dense, idx[..., None], axis=-2)

    @pytest.mark.parametrize("patch,n_vec,n_patches,k", [
        (8, 16, 16, 4),
        (16, 192, 12, 3),      # n_vec not a multiple of 128
        (16, 32, 16, 16),      # k == P (compact degenerates to dense)
    ])
    def test_sparse_matches_dense_gather_random_sets(self, patch, n_vec, n_patches, k):
        spec = proj.PatchSpec(patch_h=patch, patch_w=patch, n_vectors=n_vec)
        patches = jax.random.uniform(KEY, (2, n_patches, patch * patch))
        w = jax.random.normal(jax.random.PRNGKey(1), (n_vec, patch * patch)) * 2.0
        idx = jax.random.permutation(
            jax.random.PRNGKey(2), jnp.arange(n_patches)
        )[None, :k].repeat(2, 0)
        out_s = ops.ip2_project_sparse(patches, w, idx, spec, interpret=True)
        want = self._dense_gather(patches, w, idx, spec)
        assert out_s.shape == (2, k, n_vec)
        np.testing.assert_allclose(np.asarray(out_s), np.asarray(want), atol=1e-5)

    def test_sparse_with_fused_adc_and_bias(self):
        spec = proj.PatchSpec(patch_h=8, patch_w=8, n_vectors=24)
        adc = adc_mod.ADCSpec(bits=6)
        patches = jax.random.uniform(KEY, (3, 9, 64))
        w = jax.random.normal(jax.random.PRNGKey(1), (24, 64)) * 3.0
        bias = jax.random.normal(jax.random.PRNGKey(2), (24,)) * 0.1
        idx = jnp.array([[0, 8, 4], [7, 1, 2], [3, 3, 5]], jnp.int32)
        out_s = ops.ip2_project_sparse(
            patches, w, idx, spec, adc=adc, bias=bias, interpret=True
        )
        want = self._dense_gather(patches, w, idx, spec, adc=adc, bias=bias)
        np.testing.assert_allclose(np.asarray(out_s), np.asarray(want), atol=1e-5)

    def test_sparse_repeated_indices_fewer_than_k_active(self):
        """< k active patches: the selector pads by repeating indices; the
        kernel must simply project the repeated bank again."""
        spec = proj.PatchSpec(patch_h=8, patch_w=8, n_vectors=16)
        patches = jax.random.uniform(KEY, (1, 8, 64))
        w = jax.random.normal(jax.random.PRNGKey(1), (16, 64))
        idx = jnp.array([[2, 5, 5, 5]], jnp.int32)      # only 2 distinct active
        out_s = ops.ip2_project_sparse(patches, w, idx, spec, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out_s[0, 1]), np.asarray(out_s[0, 2]), atol=0
        )
        want = self._dense_gather(patches, w, idx, spec)
        np.testing.assert_allclose(np.asarray(out_s), np.asarray(want), atol=1e-5)

    def test_sparse_kernel_vs_padded_oracle(self):
        """Direct padded-shape parity: pallas entry vs ref oracle, at every
        row-bank size dividing the row table."""
        from repro.kernels.ip2_project_sparse import ip2_project_sparse_pallas

        params = IP2KernelParams(n2=64, adc_enable=False)
        patches = jax.random.uniform(KEY, (16, 256))
        w = jax.random.normal(jax.random.PRNGKey(1), (256, 128))
        bias = jnp.zeros((128,))
        idx = jnp.array([3, 15, 0, 7, 7, 11], jnp.int32)
        want = ref.ip2_project_sparse_ref(idx, patches, w, bias, params)
        for block_r in (1, 2, 3, 6):
            got = ip2_project_sparse_pallas(
                idx, patches, w, bias[None, :], params,
                block_r=block_r, block_m=128, block_k=256, interpret=True,
            )
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_sparse_block_r_does_not_change_results(self):
        """The wrapper's sublane-aligned row banking (block_r) is a pure
        perf knob: any bank size (including non-dividing ones, padded and
        sliced internally) yields identical features."""
        spec = proj.PatchSpec(patch_h=8, patch_w=8, n_vectors=24)
        patches = jax.random.uniform(KEY, (3, 9, 64))
        w = jax.random.normal(jax.random.PRNGKey(1), (24, 64)) * 2.0
        idx = jnp.array([[0, 8, 4], [7, 1, 2], [3, 3, 5]], jnp.int32)
        base = ops.ip2_project_sparse(patches, w, idx, spec,
                                      block_r=1, interpret=True)
        for block_r in (None, 2, 4, 8, 16):
            out = ops.ip2_project_sparse(patches, w, idx, spec,
                                         block_r=block_r, interpret=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                       atol=1e-6)

    def test_kernels_emit_wire_codes(self):
        """codes=True: both projection kernels emit int8 ADC codes from the
        fused epilogue whose dequant matches the float fused-ADC output
        (within fused-multiply-add reassociation, far below 1 LSB)."""
        from repro.core.adc import dequantize, readout_scale_zero

        spec = proj.PatchSpec(patch_h=8, patch_w=8, n_vectors=24)
        adc = adc_mod.ADCSpec(bits=8)
        patches = jax.random.uniform(KEY, (2, 9, 64))
        w = jax.random.normal(jax.random.PRNGKey(1), (24, 64)) * 3.0
        bias = jax.random.normal(jax.random.PRNGKey(2), (24,)) * 0.1
        scale, zero = readout_scale_zero(spec.summer.v_ref, bias, adc)

        f_dense = ops.ip2_project(patches, w, spec, adc=adc, bias=bias,
                                  interpret=True)
        c_dense = ops.ip2_project(patches, w, spec, adc=adc, bias=bias,
                                  codes=True, interpret=True)
        assert c_dense.dtype == jnp.int8
        np.testing.assert_allclose(np.asarray(dequantize(c_dense, scale, zero)),
                                   np.asarray(f_dense), atol=1e-6)

        idx = jnp.array([[0, 8, 4], [7, 1, 2]], jnp.int32)
        c_sparse = ops.ip2_project_sparse(patches, w, idx, spec, adc=adc,
                                          bias=bias, codes=True, interpret=True)
        assert c_sparse.dtype == jnp.int8
        np.testing.assert_array_equal(
            np.asarray(c_sparse),
            np.asarray(jnp.take_along_axis(c_dense, idx[..., None], axis=-2)),
        )

    @pytest.mark.parametrize("k", [1, 3, 9])     # single saccade .. k == P
    @pytest.mark.parametrize("bp_r,bm,bk", [
        (1, 128, 128),
        (8, 128, 256),       # shipped defaults
        (8, 256, 128),       # non-divisible M=50 and N2=576 pad both blocks
        (16, 512, 256),      # the roofline-picked m_steps=1 shape
    ])
    def test_block_sweep_parity_battery_all_three_kernels(self, k, bp_r, bm, bk):
        """Satellite battery (DESIGN.md §11): the dense kernel, the sparse
        gather kernel, and the ragged megakernel path emit BITWISE-identical
        int8 wire codes for the same selection at every block tiling —
        including pad remainders (M=50, N2=576) and the k=1 / k=P edges.
        ``bp_r`` doubles as block_p (dense) and block_r (sparse/ragged)."""
        spec = proj.PatchSpec(patch_h=24, patch_w=24, n_vectors=50)
        adc = adc_mod.ADCSpec(bits=8)
        patches = jax.random.uniform(KEY, (2, 9, 576))
        w = jax.random.normal(jax.random.PRNGKey(1), (50, 576)) * 2.0
        idx = jnp.stack([
            jax.random.permutation(jax.random.PRNGKey(2 + b),
                                   jnp.arange(9))[:k]
            for b in range(2)
        ])
        c_dense = ops.ip2_project(patches, w, spec, adc=adc, codes=True,
                                  block_p=bp_r, block_m=bm, block_k=bk,
                                  interpret=True)
        want = jnp.take_along_axis(c_dense, idx[..., None], axis=-2)
        c_sparse = ops.ip2_project_sparse(
            patches, w, idx, spec, adc=adc, codes=True,
            block_r=bp_r, block_m=bm, block_k=bk, interpret=True)
        np.testing.assert_array_equal(np.asarray(c_sparse), np.asarray(want))
        c_ragged = ops.ip2_project_sparse(
            patches, w, idx, spec, adc=adc, codes=True,
            row_counts=jnp.full((2,), k, jnp.int32),
            block_r=bp_r, block_m=bm, block_k=bk, interpret=True)
        np.testing.assert_array_equal(np.asarray(c_ragged), np.asarray(want))

    def test_codes_require_adc(self):
        spec = proj.PatchSpec(patch_h=8, patch_w=8, n_vectors=16)
        patches = jax.random.uniform(KEY, (1, 4, 64))
        w = jax.random.normal(KEY, (16, 64))
        with pytest.raises(ValueError, match="codes=True requires"):
            ops.ip2_project(patches, w, spec, codes=True, interpret=True)


# ---------------------------------------------------------------------------
# hypothesis property tests
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(
        n_patches=st.integers(1, 9),
        n_vec=st.integers(1, 40),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_ip2_kernel_property_allclose(n_patches, n_vec, seed):
        spec = proj.PatchSpec(patch_h=8, patch_w=8, n_vectors=n_vec)
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        patches = jax.random.uniform(k1, (n_patches, 64))
        w = jax.random.normal(k2, (n_vec, 64)) * 2.0
        out_k = ops.ip2_project(patches, w, spec, interpret=True)
        out_r = proj.analog_project_patches(patches, w, spec)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-5)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), k=st.integers(1, 9))
    def test_sparse_kernel_property_allclose(seed, k):
        """Sparse == gather(dense) for arbitrary random active sets."""
        spec = proj.PatchSpec(patch_h=8, patch_w=8, n_vectors=16)
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        patches = jax.random.uniform(k1, (9, 64))
        w = jax.random.normal(k2, (16, 64)) * 2.0
        idx = jax.random.randint(k3, (k,), 0, 9)
        out_s = ops.ip2_project_sparse(patches, w, idx, spec, interpret=True)
        dense = ops.ip2_project(patches, w, spec, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out_s), np.asarray(dense[idx]), atol=1e-5
        )

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_ip2_output_bounded_by_rails(seed):
        """Analog outputs can never exceed the voltage rails (physics)."""
        from repro.core.analog_nl import AnalogNLSpec

        spec = proj.PatchSpec(
            patch_h=8, patch_w=8, n_vectors=8, nl=AnalogNLSpec(kind="relu", v_sat=1.0)
        )
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        patches = jax.random.uniform(k1, (3, 64))
        w = jax.random.normal(k2, (8, 64)) * 50.0   # absurd weight currents
        out = ops.ip2_project(patches, w, spec, interpret=True)
        assert float(out.min()) >= 0.0 and float(out.max()) <= 1.0

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), bits=st.integers(2, 8))
    def test_pwm_monotone_property(seed, bits):
        """PWM quantization is monotone non-decreasing (a comparator ramp)."""
        from repro.core.pwm import pwm_quantize

        x = jnp.sort(jax.random.uniform(jax.random.PRNGKey(seed), (100,)))
        q = pwm_quantize(x, QuantSpec(pwm_bits=bits))
        assert bool(jnp.all(jnp.diff(q) >= 0))
