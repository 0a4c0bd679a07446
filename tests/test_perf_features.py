"""Tests for the §Perf optimizations: int8 KV cache (B2), bf16 cache
contraction (B1 — covered by decode==forward tests), all_to_all MoE
dispatch (A2), quantized backend matmul."""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models as M
from repro.configs import smoke_config
from repro.configs.base import MoEConfig

KEY = jax.random.PRNGKey(0)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_int8_kv_cache_decode_close_to_fp32():
    """int8 KV (B2) must track the fp32-cache decode within ~1.5% of the
    logit scale across a prefill + 8 decode steps."""
    cfg = dataclasses.replace(smoke_config("llama3-8b"), remat=False)
    params = M.init_params(KEY, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    logits_full, _ = M.forward(params, {"tokens": tokens}, cfg)
    scale = float(jnp.abs(logits_full).max())

    state = M.init_decode_state(cfg, M.DEFAULT_PLAN, 2, 16, cache_dtype=jnp.int8)
    lg, state = M.prefill(
        params, {"tokens": tokens[:, :8]}, cfg, M.DEFAULT_PLAN, state
    )
    errs = [float(jnp.abs(lg - logits_full[:, 7]).max())]
    for t in range(8, 16):
        lg, state = M.decode_step(params, state, tokens[:, t], jnp.int32(t), cfg)
        errs.append(float(jnp.abs(lg - logits_full[:, t]).max()))
    assert max(errs) / scale < 0.015, (max(errs), scale)


def test_int8_cache_state_has_scales():
    cfg = smoke_config("llama3-8b")
    st = M.init_decode_state(cfg, M.DEFAULT_PLAN, 2, 8, cache_dtype=jnp.int8)
    s0 = st["stacks"][0]
    assert s0["k"].dtype == jnp.int8 and "k_scale" in s0 and "v_scale" in s0
    specs = M.decode_state_specs(cfg, M.DEFAULT_PLAN, cache_dtype=jnp.int8)
    assert "k_scale" in specs["stacks"][0]


def test_moe_a2a_matches_reference_multihost():
    """A2 all_to_all dispatch == GSPMD reference on a (2,4) host mesh
    (ample capacity so no shard-local drops)."""
    code = """
        import json, dataclasses, jax, jax.numpy as jnp
        from repro.configs import smoke_config
        from repro.configs.base import MoEConfig
        from repro.models import moe as moe_mod
        from repro.models.moe_a2a import apply_moe_a2a
        cfg = smoke_config("qwen3-moe-235b-a22b")
        cfg = dataclasses.replace(cfg, moe=MoEConfig(
            n_experts=8, top_k=2, d_expert=32, capacity_factor=2.0))
        p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model)) * 0.5
        ref, aux_ref = moe_mod.apply_moe(p, x, cfg)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        out, aux = jax.jit(lambda p_, x_: apply_moe_a2a(
            p_, x_, cfg, mesh, ("data",), "model"))(p, x)
        g = jax.grad(lambda p_: apply_moe_a2a(
            p_, x, cfg, mesh, ("data",), "model")[0].sum())(p)
        print(json.dumps({
            "diff": float(jnp.abs(out - ref).max()),
            "aux_diff": abs(float(aux) - float(aux_ref)),
            "gnorm": float(jnp.linalg.norm(g["w_gate"])),
        }))
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["diff"] < 1e-5, res
    assert res["aux_diff"] < 1e-6, res
    assert res["gnorm"] > 0, res


def test_quant_matmul_backend_projection():
    """Beyond-paper int8 path on a backend projection keeps relative error
    at the quantization floor for realistic activations."""
    from repro.kernels import ops

    x = jax.random.normal(KEY, (7, 64)) * 0.3
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 96)) * 0.1
    w8, sw = ops.quantize_weights_int8(w)
    y = ops.quant_matmul(x, w8, sw, interpret=True)
    rel = float(jnp.abs(y - x @ w).max() / jnp.abs(x @ w).max())
    assert rel < 0.03


def test_mlstm_chunkwise_matches_parallel():
    """§Perf X1: the chunked O(S·L) form must equal the O(S²) parallel form
    and produce the exact fold-state the decode path consumes."""
    from repro.models.xlstm import mlstm_chunkwise, mlstm_final_state, mlstm_parallel

    ks = jax.random.split(KEY, 5)
    b, s, nh, dh = 2, 37, 4, 16
    q = jax.random.normal(ks[0], (b, s, nh, dh))
    k = jax.random.normal(ks[1], (b, s, nh, dh))
    v = jax.random.normal(ks[2], (b, s, nh, dh))
    i = jax.random.normal(ks[3], (b, s, nh)) * 2
    f = jax.random.normal(ks[4], (b, s, nh)) * 2 + 2
    hp = mlstm_parallel(q, k, v, i, f)
    ref_cell = mlstm_final_state(k, v, i, f)
    for chunk in (8, 16, 64):
        hc, cell = mlstm_chunkwise(q, k, v, i, f, chunk)
        np.testing.assert_allclose(np.asarray(hc), np.asarray(hp), atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(cell["C"]), np.asarray(ref_cell["C"]), atol=1e-4
        )


def test_xlstm_chunked_forward_matches_default():
    """Model-level: xlstm with xlstm_chunk set computes the same logits."""
    cfg = dataclasses.replace(smoke_config("xlstm-1.3b"), remat=False)
    params = M.init_params(KEY, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab)
    a, _ = M.forward(params, {"tokens": tokens}, cfg)
    b_, _ = M.forward(
        params, {"tokens": tokens}, dataclasses.replace(cfg, xlstm_chunk=8)
    )
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4)
