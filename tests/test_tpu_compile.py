"""The main path's Pallas kernels and engine steps compile for a TPU v5e.

Interpret mode cannot see what the chip's compiler refuses (block shapes
off the (8, 128) tiling, unsupported operand types), so each kernel and
the served engine step is compiled here for a described — not attached —
v5e chip at the ``ip2-vit`` widths: 256x256 frames, 32x32 patches (1024
pixels), 192 vectors, k=16 of 64 patches, d_model 256, 4 heads; the
sensor kernel also at the ``ip2-2mpix`` frames (16 slots of 1024x2048).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the test workers all
import this file. Each test compiles in the test's own process.
"""

from __future__ import annotations

import os
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core import adc as adc_mod
from repro.core import projection as proj
from repro.core.temporal import TemporalSpec
from repro.kernels import ops
from repro.kernels.vit_delta_attention import delta_attention_pallas
from repro.models.vit import init_vit, prepare_quant_embed, vit_config_from
from repro.serve import telemetry
from repro.serve.engine import SaccadeEngine

SLOTS, P, N2, M, K, D, H = 8, 64, 1024, 192, 16, 256, 4
SPEC = proj.PatchSpec(patch_h=32, patch_w=32, n_vectors=M)
ADC = adc_mod.ADCSpec()


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_kernels(monkeypatch):
    """Kernels called with ``interpret=None`` resolve it from the backend,
    which is the CPU here: steer them to the chip's compiler."""
    monkeypatch.setattr(
        ops, "_auto_interpret", lambda i: False if i is None else i)


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_case(name):
    """(fn, arg shapes) of one kernel wrapper at ip2-vit widths."""
    f32, i32 = jnp.float32, jnp.int32
    if name == "ip2_project":
        return (lambda x, w: ops.ip2_project(
                    x, w, SPEC, adc=ADC, codes=True, interpret=False),
                [((SLOTS * K, N2), f32), ((M, N2), f32)])
    if name == "ip2_project_sparse":
        return (lambda x, w, i: ops.ip2_project_sparse(
                    x, w, i, SPEC, adc=ADC, codes=True, interpret=False),
                [((SLOTS, P, N2), f32), ((M, N2), f32), ((SLOTS, K), i32)])
    if name.startswith("ragged"):
        # the temporal gate's stale rows: k, or a recompute budget under
        # one 8-row bank
        j = 3 if name == "ragged_j3" else K
        return (lambda x, w, c: ops.ip2_project_sparse(
                    x, w, ops._identity_indices(x), SPEC, adc=ADC,
                    codes=True, row_counts=c, interpret=False),
                [((SLOTS, j, N2), f32), ((M, N2), f32), ((SLOTS,), i32)])
    if name == "fused":
        return (lambda x, w, i, w8, sw, c: ops.ip2_fused_embed(
                    x, w, i, SPEC, ADC, w8, sw, row_counts=c,
                    interpret=False),
                [((SLOTS, P, N2), f32), ((M, N2), f32), ((SLOTS, K), i32),
                 ((M, D), jnp.int8), ((D,), f32), ((SLOTS,), i32)])
    if name.startswith("bayer_frame"):
        # the sensor's optics+mosaic; 2mpix: 16 slots of 1024x2048, in
        # bands of 128 rows with halo tiles and edge lane tiles
        shape = ((16, 1024, 2048, 3) if name == "bayer_frame_2mpix"
                 else (SLOTS, 256, 256, 3))
        return (lambda x: ops.bayer_frame(x, 0.5, interpret=False),
                [(shape, f32)])
    if name == "quant_matmul":
        return (lambda a, sa, w8, sw: ops.quant_matmul_pre(
                    a, sa, w8, sw, interpret=False),
                [((SLOTS, K, M), jnp.int8), ((), f32), ((M, D), jnp.int8),
                 ((D,), f32)])
    assert name == "vit_delta_attention"
    qkv = ((SLOTS, K, H, D // H), f32)
    return (lambda q, k, v, m, c: delta_attention_pallas(
                q, k, v, m, c, block_q=8, interpret=False),
            [qkv, qkv, qkv, ((SLOTS, K), jnp.bool_), ((SLOTS,), i32)])


@pytest.mark.parametrize("name", [
    "ip2_project", "ip2_project_sparse", "ragged", "ragged_j3", "fused",
    "quant_matmul", "vit_delta_attention", "bayer_frame", "bayer_frame_2mpix",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name)
    shapes = [_shape(one_chip, s, d) for s, d in args]
    hlo = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in hlo


def _engine(mode: str) -> SaccadeEngine:
    model = get_config("ip2-vit")
    if mode == "codes":
        gate = TemporalSpec(delta_threshold=1e-4)
        cfg = vit_config_from(
            model, frontend_kw=dict(temporal=gate), quant_embed=True,
            saliency_layers="last", delta_kernel=True)
        fc = cfg.frontend
        kw = dict(project_fn=ops.ip2_codes_fn(fc.patch, fc.adc,
                                              interpret=False),
                  temporal=True, backend_delta=True)
    else:
        cfg = vit_config_from(model, quant_embed=True, fused_embed=True,
                              saliency_layers="last")
        kw = {}
    params = prepare_quant_embed(init_vit(jax.random.PRNGKey(0), cfg))
    return SaccadeEngine(cfg, params, capacity=SLOTS, **kw)


@pytest.mark.parametrize("mode,n_kernels", [
    # the sensor's optics+mosaic, then ragged projection + w8a8 embed +
    # delta attention on 5 of 6 layers
    ("codes", 8),
    ("fused", 2),
])
def test_engine_step_compiles_for_v5e(one_chip, compile_kernels, mode,
                                      n_kernels):
    eng = _engine(mode)
    st = eng._state
    args = (eng.params, eng._frames_dev, jnp.zeros((SLOTS,), bool), st)
    shapes = jax.tree.map(
        lambda x: _shape(one_chip, x.shape, x.dtype), args)
    compiled = eng._step_fn.lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == n_kernels
    # the sensor kernel is the sensor layer's in the device trace's map
    scopes = telemetry.Telemetry().record_scopes(text)
    sensor = [n for n in scopes if n.startswith("bayer_frame_pallas")]
    assert sensor and {scopes[n] for n in sensor} == {"sensor"}
    # the whole step fits one chip's 16 GiB with room to spare
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 2**30
    assert eng.n_traces == 1


def test_repo_never_allows_multiple_libtpu_loads():
    """The libtpu lock is what keeps two processes off one chip: nothing
    in the repo may switch it off."""
    flag = "ALLOW_MULTIPLE_" + "LIBTPU_LOAD"
    root = pathlib.Path(__file__).resolve().parents[1]
    hits = []
    for path in root.rglob("*"):
        if ".git" in path.parts or not path.is_file():
            continue
        if path.suffix not in (".py", ".cfg", ".ini", ".toml", ".sh",
                               ".yml", ".yaml", ".json"):
            continue
        if flag in path.read_text(errors="ignore"):
            hits.append(str(path.relative_to(root)))
    assert hits == []
