"""Distribution tests on host devices: sharded train step correctness
(vs single-device reference), pipeline parallelism, compressed gradient
all-reduce, spec fitting, elastic restore.

These tests need multiple host devices; they re-exec themselves in a
subprocess with XLA_FLAGS so the main pytest process keeps 1 device (the
assignment requires smoke tests to see 1 device).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n: int = 8) -> dict:
    """Run `code` in a subprocess with n host devices; code must print a
    JSON dict as its last line."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_train_step_matches_single_device():
    """jit train step on a (2,2) mesh == the same step on 1 device."""
    res = run_with_devices("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro import models as M
        from repro.configs import smoke_config
        from repro.launch.shardings import plan_for, shardings_for, constrainer_ctx
        from repro.launch.specs import batch_spec_shardings
        from repro.optim import AdamWConfig, init_opt_state, opt_state_specs
        from repro.train.train_step import make_train_step
        from repro.configs.base import SHAPES
        import dataclasses

        cfg = smoke_config("llama3-8b")
        key = jax.random.PRNGKey(0)
        opt = AdamWConfig(lr=1e-3)
        batch = {"tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab)}

        # single-device reference
        params = M.init_params(key, cfg)
        opt_state = init_opt_state(params, opt)
        step_ref = jax.jit(make_train_step(cfg, M.DEFAULT_PLAN, opt,
                                           compute_dtype=jnp.float32))
        p_ref, _, m_ref = step_ref(params, opt_state, batch)

        # sharded
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        plan = plan_for(cfg, mesh)
        params2 = M.init_params(key, cfg, plan)   # same shapes (tp padding no-op: tp=2 divides)
        opt2 = init_opt_state(params2, opt)
        pspecs = M.param_specs(cfg, plan)
        p_sh = shardings_for(pspecs, params2, mesh)
        o_sh = shardings_for(opt_state_specs(pspecs), opt2, mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P
        b_sh = {"tokens": NamedSharding(mesh, P(("data",), None))}
        with constrainer_ctx(mesh, plan):
            stepfn = jax.jit(make_train_step(cfg, plan, opt, compute_dtype=jnp.float32),
                             in_shardings=(p_sh, o_sh, b_sh),
                             out_shardings=(p_sh, o_sh, None))
            p_sh_out, _, m_sh = stepfn(params2, opt2, batch)

        diffs = [float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
                 for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh_out))]
        print(json.dumps({
            "loss_ref": float(m_ref["loss"]), "loss_sh": float(m_sh["loss"]),
            "max_param_diff": max(diffs),
        }))
    """, n=4)
    assert abs(res["loss_ref"] - res["loss_sh"]) < 2e-4, res
    assert res["max_param_diff"] < 5e-5, res


def test_head_geometry_padding():
    from repro.configs import get_config
    from repro.models.attention import head_geometry
    from repro.models.layers import ParallelPlan

    plan16 = ParallelPlan(tp=16)
    cases = {
        "llama3-8b": (32, 16),        # q ok, kv lcm(8,16)=16
        "qwen2.5-32b": (48, 16),      # q 40 -> pad 48
        "smollm-135m": (16, 16),      # q 9 -> 16; lcm(3,16)=48 !| 16 -> MHA-ize
        "whisper-tiny": (16, 16),
        "qwen3-moe-235b-a22b": (64, 16),
        "recurrentgemma-2b": (16, 16),  # MQA replicated
    }
    for arch, want in cases.items():
        got = head_geometry(get_config(arch), plan16)
        assert got == want, (arch, got, want)
        hq, hkv = got
        assert hq % hkv == 0      # grouped attention divisibility invariant


def test_fit_spec_drops_indivisible():
    res = run_with_devices("""
        import json, jax
        from jax.sharding import PartitionSpec as P
        from repro.launch.shardings import fit_spec
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        a = fit_spec(P("data", "model"), (4, 6), mesh)   # 6 % 2 == 0 -> keep
        b = fit_spec(P("data", "model"), (4, 7), mesh)   # 7 % 2 != 0 -> drop
        c = fit_spec(P(("data", "model"), None), (1, 8), mesh)  # batch 1 -> drop
        print(json.dumps({"a": str(a), "b": str(b), "c": str(c)}))
    """, n=4)
    assert "model" in res["a"]
    assert "model" not in res["b"]
    assert "data" not in res["c"]


def test_pipeline_parallel_matches_sequential():
    res = run_with_devices("""
        import json, jax, jax.numpy as jnp
        from repro.distributed.pipeline import pipeline_forward, split_layers_to_stages
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        L, D = 8, 16
        w = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.4
        def stage_fn(params, x):
            def body(c, p): return jnp.tanh(c @ p), None
            return jax.lax.scan(body, x, params)[0]
        mbs = jax.random.normal(jax.random.PRNGKey(1), (6, 3, D))
        out = pipeline_forward(split_layers_to_stages(w, 4), mbs, stage_fn, mesh)
        def seq(x):
            def body(c, p): return jnp.tanh(c @ p), None
            return jax.lax.scan(body, x, w)[0]
        ref = jnp.stack([seq(mbs[i]) for i in range(6)])
        gpp = jax.grad(lambda w_: jnp.sum(pipeline_forward(
            split_layers_to_stages(w_, 4), mbs, stage_fn, mesh) ** 2))(w)
        gseq = jax.grad(lambda w_: jnp.sum(jnp.stack(
            [jax.lax.scan(lambda c, p: (jnp.tanh(c @ p), None), mbs[i], w_)[0]
             for i in range(6)]) ** 2))(w)
        print(json.dumps({
            "fwd_err": float(jnp.abs(out - ref).max()),
            "grad_err": float(jnp.abs(gpp - gseq).max()),
        }))
    """, n=4)
    assert res["fwd_err"] < 1e-6
    assert res["grad_err"] < 1e-5


def test_pipeline_fewer_microbatches_than_stages():
    """The GPipe schedule must stay correct when the pipe is mostly bubble
    (n_micro < n_stages) — the tail/injection masking, not just the steady
    state, is what this exercises."""
    res = run_with_devices("""
        import json, jax, jax.numpy as jnp
        from repro.distributed.pipeline import pipeline_forward, split_layers_to_stages
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        L, D = 4, 8
        w = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.4
        def stage_fn(params, x):
            def body(c, p): return jnp.tanh(c @ p), None
            return jax.lax.scan(body, x, params)[0]
        mbs = jax.random.normal(jax.random.PRNGKey(1), (2, 3, D))   # 2 < 4 stages
        out = pipeline_forward(split_layers_to_stages(w, 4), mbs, stage_fn, mesh)
        def seq(x):
            def body(c, p): return jnp.tanh(c @ p), None
            return jax.lax.scan(body, x, w)[0]
        ref = jnp.stack([seq(mbs[i]) for i in range(2)])
        print(json.dumps({"fwd_err": float(jnp.abs(out - ref).max())}))
    """, n=4)
    assert res["fwd_err"] < 1e-6


def test_pipeline_bubble_nan_does_not_poison_output():
    """PR-7 satellite regression: bubble ticks feed a ZERO carry into
    stage_fn; a stage_fn that divides by its input norm emits NaN there.
    The final masking must select (jnp.where), not multiply — with the
    old ``psum(out * is_last)``, ``NaN * 0 = NaN`` poisons every real
    output through the psum."""
    res = run_with_devices("""
        import json, jax, jax.numpy as jnp
        from repro.distributed.pipeline import pipeline_forward, split_layers_to_stages
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        L, D = 4, 8
        w = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.4
        def body(c, p):
            nrm = jnp.sqrt(jnp.sum(c * c))
            return jnp.tanh((c / nrm) @ p), None  # NaN on the zero bubble carry
        def stage_fn(params, x):
            return jax.lax.scan(body, x, params)[0]
        mbs = jax.random.normal(jax.random.PRNGKey(1), (6, 3, D))
        out = pipeline_forward(split_layers_to_stages(w, 4), mbs, stage_fn, mesh)
        def seq(x):
            return jax.lax.scan(body, x, w)[0]
        ref = jnp.stack([seq(mbs[i]) for i in range(6)])
        print(json.dumps({
            "finite": bool(jnp.isfinite(out).all()),
            "fwd_err": float(jnp.abs(out - ref).max()),
        }))
    """, n=4)
    assert res["finite"], "bubble-tick NaN poisoned the masked psum"
    assert res["fwd_err"] < 1e-6


def test_engine_sharded_slots_match_unsharded_zero_recompiles():
    """SaccadeEngine with the slot axis shard_map'd over 4 host devices:
    identical logits to the unsharded engine, state physically spread over
    the mesh, and one compilation across an admit→evict→admit cycle."""
    res = run_with_devices("""
        import json
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.core.frontend import FrontendConfig
        from repro.core.projection import PatchSpec
        from repro.data.pipeline import SceneStream
        from repro.launch.mesh import make_host_mesh
        from repro.models.vit import ViTConfig, init_vit
        from repro.serve.engine import SaccadeEngine

        fcfg = FrontendConfig(image_h=64, image_w=64,
                              patch=PatchSpec(patch_h=16, patch_w=16, n_vectors=32),
                              active_fraction=0.25)
        cfg = ViTConfig(frontend=fcfg, n_layers=1, d_model=32, n_heads=2, d_ff=64)
        params = init_vit(jax.random.PRNGKey(0), cfg)
        stream = SceneStream(image=64)
        mesh = make_host_mesh(data=4, model=1)

        e_sh = SaccadeEngine(cfg, params, capacity=8, mesh=mesh)
        e_ref = SaccadeEngine(cfg, params, capacity=8)
        for s in range(5):
            e_sh.admit(s); e_ref.admit(s)
        err = 0.0
        for t in range(3):
            rgb, _ = stream.batch(t, 5)
            frames = {i: rgb[i] for i in range(5)}
            o1, o2 = e_sh.step(frames), e_ref.step(frames)
            err = max(err, max(float(np.abs(o1[s] - o2[s]).max()) for s in frames))
        # churn: evict + admit into the freed slot, then serve again
        e_sh.evict(0); e_sh.admit(99)
        rgb, _ = stream.batch(7, 5)
        e_sh.step({99: rgb[0], **{i: rgb[i] for i in range(1, 5)}})

        # indivisible capacity (5 % 4 != 0): engine must fall back to a
        # plain jit, NOT shard_map with replicated specs (n_dev x compute)
        e_odd = SaccadeEngine(cfg, params, capacity=5, mesh=mesh)
        for s in range(3):
            e_odd.admit(s)
        rgb, _ = stream.batch(2, 3)
        frames = {i: rgb[i] for i in range(3)}
        o_odd = e_odd.step(frames)
        o_ref2 = {}
        e_ref2 = SaccadeEngine(cfg, params, capacity=5)
        for s in range(3):
            e_ref2.admit(s)
        o_ref2 = e_ref2.step(frames)
        odd_err = max(float(np.abs(o_odd[s] - o_ref2[s]).max()) for s in frames)
        print(json.dumps({
            "err": err,
            "state_devices": len(e_sh.state.ema.sharding.device_set),
            "traces_sharded": e_sh.n_traces,
            "traces_ref": e_ref.n_traces,
            "odd_sharded": e_odd._slot_spec != jax.sharding.PartitionSpec(),
            "odd_err": odd_err,
        }))
    """, n=4)
    assert res["err"] < 1e-5, res
    assert res["state_devices"] == 4, res          # slot axis really sharded
    assert res["traces_sharded"] == 1, res         # admit/evict: no recompile
    assert res["traces_ref"] == 1, res
    assert res["odd_sharded"] is False, res        # indivisible -> plain jit
    assert res["odd_err"] < 1e-5, res


def test_temporal_engine_sharded_matches_unsharded():
    """SaccadeEngine(temporal=True) with the slot axis shard_map'd: the
    per-slot FeatureCache shards with the rest of StreamState, logits and
    recompute fractions match the unsharded engine on a static scene
    (reuse kicks in identically), still one compile."""
    res = run_with_devices("""
        import json
        import numpy as np
        import jax
        from repro.core.frontend import FrontendConfig
        from repro.core.projection import PatchSpec
        from repro.core.temporal import TemporalSpec
        from repro.data.pipeline import SceneStream
        from repro.launch.mesh import make_host_mesh
        from repro.models.vit import ViTConfig, init_vit
        from repro.serve.engine import SaccadeEngine

        fcfg = FrontendConfig(image_h=64, image_w=64,
                              patch=PatchSpec(patch_h=16, patch_w=16, n_vectors=32),
                              active_fraction=0.25,
                              temporal=TemporalSpec(delta_threshold=1e-5))
        cfg = ViTConfig(frontend=fcfg, n_layers=1, d_model=32, n_heads=2, d_ff=64)
        params = init_vit(jax.random.PRNGKey(0), cfg)
        stream = SceneStream(image=64)
        mesh = make_host_mesh(data=4, model=1)

        e_sh = SaccadeEngine(cfg, params, capacity=4, mesh=mesh, temporal=True)
        e_ref = SaccadeEngine(cfg, params, capacity=4, temporal=True)
        for s in range(3):
            e_sh.admit(s); e_ref.admit(s)
        frame0 = stream.batch(0, 3)[0]
        frames = {i: frame0[i] for i in range(3)}
        err = 0.0
        for t in range(4):                    # static scene: reuse kicks in
            o = e_sh.step(frames); r = e_ref.step(frames)
            err = max(err, max(float(np.abs(o[s] - r[s]).max()) for s in frames))
        print(json.dumps({
            "err": err,
            "cache_devices": len(e_sh.state.cache.features.sharding.device_set),
            "fr_sh": [e_sh.recompute_fraction(s) for s in range(3)],
            "fr_ref": [e_ref.recompute_fraction(s) for s in range(3)],
            "traces": e_sh.n_traces,
        }))
    """, n=4)
    assert res["err"] < 1e-5, res
    assert res["cache_devices"] == 4, res        # cache really sharded
    assert res["fr_sh"] == res["fr_ref"], res    # identical reuse decisions
    assert res["fr_sh"] == [0.0, 0.0, 0.0], res  # static scene: no recompute
    assert res["traces"] == 1, res


def test_governed_engine_sharded_matches_unsharded():
    """Governed engine (DESIGN.md §10) with the slot axis shard_map'd:
    the per-slot energy meters and governor controls shard with the rest
    of StreamState (the control law is per-slot — no collectives), and
    measured power / caps / tiers match the unsharded governed engine.
    Still one compile."""
    res = run_with_devices("""
        import json
        import numpy as np
        import jax
        from repro.core.frontend import FrontendConfig
        from repro.core.projection import PatchSpec
        from repro.core.temporal import TemporalSpec
        from repro.launch.mesh import make_host_mesh
        from repro.models.vit import ViTConfig, init_vit
        from repro.serve.engine import SaccadeEngine
        from repro.serve.governor import GovernorSpec

        fcfg = FrontendConfig(image_h=64, image_w=64, aa_cutoff=None,
                              patch=PatchSpec(patch_h=8, patch_w=8, n_vectors=64),
                              active_fraction=0.25,
                              temporal=TemporalSpec(delta_threshold=1e-4))
        cfg = ViTConfig(frontend=fcfg, n_layers=1, d_model=32, n_heads=2, d_ff=64)
        params = init_vit(jax.random.PRNGKey(0), cfg)
        mesh = make_host_mesh(data=4, model=1)
        gov = GovernorSpec(budget_mw=0.30)
        scenes = np.asarray(jax.random.uniform(
            jax.random.PRNGKey(1), (12, 64, 64, 3)))

        e_sh = SaccadeEngine(cfg, params, capacity=4, mesh=mesh,
                             temporal=True, governor=gov)
        e_ref = SaccadeEngine(cfg, params, capacity=4, temporal=True,
                              governor=gov)
        for s in range(4):
            e_sh.admit(s); e_ref.admit(s)
        for t in range(10):                     # full motion: governor bites
            frames = {s: scenes[(t + s) % 12] for s in range(4)}
            e_sh.step(frames); e_ref.step(frames)
        print(json.dumps({
            "ctrl_devices": len(e_sh.state.controls.j_cap.sharding.device_set),
            "ev_devices": len(
                e_sh.state.events_mean.adc_conversions.sharding.device_set),
            "caps_sh": [e_sh.recompute_cap(s) for s in range(4)],
            "caps_ref": [e_ref.recompute_cap(s) for s in range(4)],
            "mw_sh": [round(e_sh.power_mw(s), 9) for s in range(4)],
            "mw_ref": [round(e_ref.power_mw(s), 9) for s in range(4)],
            "traces": e_sh.n_traces,
        }))
    """, n=4)
    assert res["ctrl_devices"] == 4, res         # controls really sharded
    assert res["ev_devices"] == 4, res           # meters really sharded
    assert res["caps_sh"] == res["caps_ref"], res
    assert res["mw_sh"] == res["mw_ref"], res
    assert res["traces"] == 1, res


def test_compressed_allreduce_and_error_feedback():
    res = run_with_devices("""
        import json, jax, jax.numpy as jnp
        from repro.optim.compression import make_compressed_allreduce
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        fn = make_compressed_allreduce(mesh, "data")
        g = jax.random.normal(jax.random.PRNGKey(2), (8, 256))
        err = {"g": jnp.zeros((8, 256))}
        # accumulate over steps: error feedback drives the running mean bias -> 0
        tot_exact, tot_comp = jnp.zeros(256), jnp.zeros(256)
        for s in range(20):
            gs = g * (1.0 + 0.01 * s)
            mean, err = fn({"g": gs}, err)
            tot_comp = tot_comp + mean["g"][0]
            tot_exact = tot_exact + gs.mean(0)
        one_rel = float(jnp.abs(mean["g"][0] - gs.mean(0)).max() / jnp.abs(gs.mean(0)).max())
        cum_rel = float(jnp.abs(tot_comp - tot_exact).max() / jnp.abs(tot_exact).max())
        print(json.dumps({"one_rel": one_rel, "cum_rel": cum_rel}))
    """, n=8)
    assert res["one_rel"] < 0.03
    assert res["cum_rel"] < res["one_rel"]   # EF cancels error over steps


def test_elastic_restore_subprocess(tmp_path):
    res = run_with_devices(f"""
        import json, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import CheckpointManager
        mesh4 = jax.make_mesh((4, 1), ("data", "model"))
        mesh2 = jax.make_mesh((2, 2), ("data", "model"))
        x = jnp.arange(64.0).reshape(8, 8)
        xs = jax.device_put(x, NamedSharding(mesh4, P("data", None)))
        cm = CheckpointManager({str(tmp_path)!r})
        cm.save(1, {{"x": xs}}, blocking=True)
        target = NamedSharding(mesh2, P("data", "model"))
        restored, _ = cm.restore({{"x": x}}, shardings={{"x": target}})
        print(json.dumps({{
            "equal": bool(jnp.array_equal(restored["x"], x)),
            "resharded": restored["x"].sharding == target,
        }}))
    """, n=4)
    assert res["equal"] and res["resharded"]
