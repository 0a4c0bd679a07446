"""Delta-gated incremental backend (DESIGN.md §14): eps=0 bitwise
reproduction of the dense encoder over closed saccade-loop trajectories,
the fully-cached skip path, the ragged stale-prefix Pallas kernel, the
eps>0 error budget, and the engine-level BackendCache discipline.

Bitwise methodology: XLA fuses value-identical subgraphs differently
depending on their consumers (even two calls to the same function inside
one program can differ by 1-2 ulp), so dense-vs-delta bitwise equality
is asserted the only way it is well-defined — both encoders run as
STANDALONE compiled programs over the same MATERIALIZED wire block
(``cf``). Cross-program engine-vs-oracle comparisons follow the repo's
house discipline (atol=1e-5), same as tests/test_serve_engine.py.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import saliency as sal
from repro.core.frontend import FrontendConfig, apply_frontend
from repro.core.projection import PatchSpec
from repro.core.switched_cap import SummerSpec
from repro.core.temporal import TemporalSpec, init_feature_cache
from repro.data.pipeline import SceneStream
from repro.models import vit as vit_mod
from repro.models.backend_delta import (
    BackendCache, delta_forward, init_backend_cache, wipe_rows,
)
from repro.models.vit import ViTConfig, init_vit, vit_forward_compact
from repro.serve.engine import SaccadeEngine
from repro.serve import governor as gov_mod
from repro.serve.serve_step import (
    make_bootstrap_indices, make_saccade_step, saccade_scores,
)

KEY = jax.random.PRNGKey(0)


def _cfg(**kw):
    # passive droop-free summer: held gain is exactly 1.0 across frames,
    # so a static scene's wire rows are bitwise stable (the backend reuse
    # precondition); delta_threshold > 0 turns the temporal gate ON
    fcfg = FrontendConfig(
        image_h=64, image_w=64,
        patch=PatchSpec(patch_h=16, patch_w=16, n_vectors=32,
                        summer=SummerSpec(mode="passive", hold_time_s=0.0)),
        active_fraction=0.25,
        temporal=TemporalSpec(delta_threshold=1e-3),
    )
    base = dict(frontend=fcfg, n_layers=2, d_model=32, n_heads=2, d_ff=64)
    base.update(kw)
    return ViTConfig(**base)


@pytest.fixture(scope="module")
def served():
    cfg = _cfg()
    return cfg, init_vit(KEY, cfg)


def _embed(params, cf, cfg):
    return (vit_mod._embed_tokens(params, cf, cfg)
            + params["pos"][cf.indices])


def _make_progs(cfg):
    """The bitwise harness: frontend, dense encoder, delta encoder as
    three standalone programs sharing materialized wire blocks."""

    @jax.jit
    def frontend(params, rgb, idx, tcache):
        return apply_frontend(params["ip2"], rgb, cfg.frontend,
                              indices=idx, mode="compact", cache=tcache)

    @jax.jit
    def dense_enc(params, cf):
        x = _embed(params, cf, cfg)
        return vit_mod._encoder(params, x, cfg, cf.valid)

    @jax.jit
    def delta_enc(params, cf, bc, eps):
        return delta_forward(params, cfg, cf,
                             lambda: _embed(params, cf, cfg), bc, eps)

    return frontend, dense_enc, delta_enc


def _select(cf, received, cfg, explore=0.1):
    rec = jnp.where(cf.valid, received, 0.0)
    b = jnp.arange(rec.shape[0])[:, None]
    saliency = jnp.zeros(
        (rec.shape[0], cfg.frontend.n_patches), jnp.float32
    ).at[b, cf.indices].max(rec)
    aux = {"saliency": saliency, "indices": cf.indices,
           "valid": cf.valid, "energy": cf.energy}
    return sal.topk_patch_indices(
        saccade_scores(aux, explore), cfg.frontend.n_active)


class TestBitwiseTrajectory:
    """The §14 acceptance gate: eps=0 reproduces the dense backend
    BITWISE over a full closed saccade-loop trajectory — through the
    compute, partial-reuse, and fully-cached skip regimes."""

    def test_eps0_bitwise_over_closed_saccade_loop(self, served):
        cfg, params = served
        k = cfg.frontend.n_active
        frontend, dense_enc, delta_enc = _make_progs(cfg)
        imgs, _ = SceneStream(image=64).batch(0, 2)
        idx = make_bootstrap_indices(cfg)(params, jnp.asarray(imgs))
        tcache = init_feature_cache(cfg.frontend, (2,))
        bc = init_backend_cache(cfg, k, (2,),
                                dtype=cfg.frontend.adc.code_dtype)
        eps0 = jnp.zeros((2,), jnp.float32)
        dense_macs = None
        macs_hist = []
        rgb = jnp.asarray(imgs)
        for t in range(16):
            if t < 8:
                # phase 1: closed loop over a slowly panning scene
                rgb = jnp.asarray(np.roll(imgs, t // 3, axis=2))
            # phase 2 (t >= 8): frozen frame + frozen gaze — the wire
            # holds bitwise and the skip regime must engage
            cf, tcache = frontend(params, rgb, idx, tcache)
            jax.block_until_ready(cf)        # materialize the shared wire
            ld, rd = dense_enc(params, cf)
            lb, rb, bc, macs = delta_enc(params, cf, bc, eps0)
            np.testing.assert_array_equal(
                np.asarray(ld), np.asarray(lb),
                err_msg=f"frame {t}: delta logits diverged from dense")
            np.testing.assert_array_equal(
                np.asarray(rd), np.asarray(rb),
                err_msg=f"frame {t}: delta saliency diverged from dense")
            macs_hist.append(np.asarray(macs))
            if dense_macs is None:
                dense_macs = float(np.max(np.asarray(macs)))
            if t < 8:
                idx = _select(cf, rd, cfg)
        # the trajectory must actually exercise all three regimes
        flat = np.stack(macs_hist)
        assert float(flat[0].max()) == dense_macs        # cold: dense work
        assert (flat[-4:] == 0.0).all(), (
            f"frozen-scene tail never reached the fully-cached skip: "
            f"{flat[-4:]}")
        mid = flat[(flat > 0.0) & (flat < dense_macs)]
        assert mid.size > 0, "trajectory never hit the partial-reuse regime"

    def test_skip_frame_serves_cached_logits_and_cache_passthrough(
            self, served):
        cfg, params = served
        k = cfg.frontend.n_active
        frontend, dense_enc, delta_enc = _make_progs(cfg)
        imgs, _ = SceneStream(image=64).batch(1, 1)
        rgb = jnp.asarray(imgs)
        idx = make_bootstrap_indices(cfg)(params, rgb)
        tcache = init_feature_cache(cfg.frontend, (1,))
        bc = init_backend_cache(cfg, k, (1,),
                                dtype=cfg.frontend.adc.code_dtype)
        eps0 = jnp.zeros((1,), jnp.float32)
        cf, tcache = frontend(params, rgb, idx, tcache)
        l1, r1, bc1, m1 = delta_enc(params, cf, bc, eps0)
        assert float(m1[0]) > 0.0
        # identical frame, identical gaze: wire holds -> whole-batch skip
        cf2, tcache = frontend(params, rgb, idx, tcache)
        l2, r2, bc2, m2 = delta_enc(params, cf2, bc1, eps0)
        assert float(m2[0]) == 0.0
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
        # the cache passes through bitwise on a skip frame
        for a, b in zip(jax.device_get(bc1), jax.device_get(bc2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_act_mask_keeps_fleet_skip_alive(self, served):
        """A held/empty slot (cache never valid) must not force a compute
        frame on an otherwise fully-cached fleet (DESIGN.md §14)."""
        cfg, params = served
        k = cfg.frontend.n_active
        frontend, _, _ = _make_progs(cfg)
        imgs, _ = SceneStream(image=64).batch(1, 2)
        rgb = jnp.asarray(imgs)
        idx = make_bootstrap_indices(cfg)(params, rgb)
        tcache = init_feature_cache(cfg.frontend, (2,))
        bc = init_backend_cache(cfg, k, (2,),
                                dtype=cfg.frontend.adc.code_dtype)
        eps0 = jnp.zeros((2,), jnp.float32)

        @jax.jit
        def delta_act(params, cf, bc, eps, act):
            return delta_forward(params, cfg, cf,
                                 lambda: _embed(params, cf, cfg), bc, eps,
                                 act=act)

        cf, tcache = frontend(params, rgb, idx, tcache)
        act = jnp.array([True, False])
        _, _, bc, m1 = delta_act(params, cf, bc, eps0, act)
        # emulate the engine's hold freeze: the held slot's cache rows
        # are DISCARDED (it never advanced), so its cache stays invalid
        bc = wipe_rows(bc, ~act)
        cf2, tcache = frontend(params, rgb, idx, tcache)
        # slot 1's cache is still invalid (it never advanced), but only
        # slot 0 is active — the whole batch must skip
        _, _, _, m2 = delta_act(params, cf2, bc, eps0, act)
        assert float(m2[0]) == 0.0 and float(m2[1]) == 0.0
        # without the mask, the invalid held slot forces compute
        _, _, _, m3 = _make_progs(cfg)[2](params, cf2, bc, eps0)
        assert float(m3[0]) > 0.0


class TestEpsBudget:
    """eps > 0 trades a measured logit-error bound for deeper reuse."""

    def _traj_error(self, cfg, params, eps_val, frames=8):
        frontend, dense_enc, delta_enc = _make_progs(cfg)
        imgs, _ = SceneStream(image=64).batch(2, 2)
        base = imgs
        idx = make_bootstrap_indices(cfg)(params, jnp.asarray(base))
        tcache = init_feature_cache(cfg.frontend, (2,))
        bc = init_backend_cache(cfg, cfg.frontend.n_active, (2,),
                                dtype=cfg.frontend.adc.code_dtype)
        eps = jnp.full((2,), eps_val, jnp.float32)
        err, total_macs = 0.0, 0.0
        for t in range(frames):
            # low-amplitude drift: the regime eps is built to absorb
            rgb = jnp.asarray(
                np.clip(base + 0.002 * t, 0.0, 1.0).astype(np.float32))
            cf, tcache = frontend(params, rgb, idx, tcache)
            jax.block_until_ready(cf)
            ld, rd = dense_enc(params, cf)
            lb, _, bc, macs = delta_enc(params, cf, bc, eps)
            err = max(err, float(jnp.max(jnp.abs(ld - lb))))
            total_macs += float(jnp.sum(macs))
            idx = _select(cf, rd, cfg)
        return err, total_macs

    def test_eps_zero_is_exact_and_error_grows_measured(self, served):
        cfg, params = served
        err0, macs0 = self._traj_error(cfg, params, 0.0)
        err_small, macs_small = self._traj_error(cfg, params, 1e-4)
        err_big, macs_big = self._traj_error(cfg, params, 5e-1)
        assert err0 == 0.0                       # the bitwise regime
        # the bound is MEASURED: a small budget keeps logits tight
        assert err_small <= 0.05, err_small
        # and a coarse budget errs more than a tight one while doing
        # no more work (snapped rows stop propagating)
        assert err_big >= err_small
        assert macs_big <= macs_small <= macs0


class TestDeltaAttentionKernel:
    """kernels/vit_delta_attention.py: ragged stale-prefix attention vs
    the einsum reference, across prefix counts including 0 and full."""

    def _ref(self, q, k, v, key_mask, q_counts):
        dh = q.shape[-1]
        qt = jnp.einsum("bshk->bhsk", q)
        kt = jnp.einsum("bshk->bhsk", k)
        vt = jnp.einsum("bshk->bhsk", v)
        sc = jnp.einsum("bhqk,bhsk->bhqs", qt, kt) / jnp.sqrt(
            jnp.asarray(dh, jnp.float32))
        sc = jnp.where(key_mask[:, None, None, :], sc, -1e30)
        o = jnp.einsum("bhqs,bhsk->bhqk", jax.nn.softmax(sc, axis=-1), vt)
        o = jnp.einsum("bhqk->bqhk", o)
        rows = jnp.arange(q.shape[1])[None, :, None, None]
        return jnp.where(rows < q_counts[:, None, None, None], o, 0.0)

    @pytest.mark.parametrize("seed", [0, 1])
    def test_interpret_parity_random_prefixes(self, seed):
        from repro.kernels.vit_delta_attention import delta_attention_pallas

        rng = np.random.default_rng(seed)
        b, s, h, dh = 3, 8, 2, 16
        q = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, s, h, dh)).astype(np.float32))
        mask = jnp.asarray(rng.random((b, s)) < 0.8)
        mask = mask.at[:, 0].set(True)          # never fully masked
        counts = jnp.asarray([0, 3, s], jnp.int32)   # empty / ragged / full
        out = delta_attention_pallas(q, k, v, mask, counts,
                                     block_q=4, interpret=True)
        ref = self._ref(q, k, v, mask, counts)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6, rtol=1e-6)
        # rows past the prefix are EXACT zeros (the caller treats them
        # as garbage and must be able to rely on the zero fill)
        assert float(jnp.max(jnp.abs(out[0]))) == 0.0

    def test_ops_wrapper_matches_encoder_attention_on_prefix(self, served):
        """ops.delta_attention (projections + kernel + output proj) must
        match the dense _encoder_attention on the covered prefix rows."""
        from repro.kernels import ops

        cfg, params = served
        lp = params["layers"][0]
        rng = np.random.default_rng(0)
        b, s, d = 2, cfg.frontend.n_active, cfg.d_model
        h = jnp.asarray(rng.normal(size=(b, s, d)).astype(np.float32))
        valid = jnp.ones((b, s), bool)
        counts = jnp.full((b,), s, jnp.int32)
        out = ops.delta_attention(lp["attn"], h, valid, counts,
                                  cfg.n_heads, block_q=4, interpret=True)
        ref, _ = vit_mod._encoder_attention(lp, h, cfg, valid,
                                            need_probs=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_pick_block_q_is_modeled_argmin(self):
        from repro.kernels.vit_delta_attention import pick_block_q
        from repro.roofline import analysis

        cands = (4, 8, 16, 32)
        for k_tok, d_model, heads in [(16, 64, 4), (64, 256, 8)]:
            got = pick_block_q(k_tok, d_model, heads, expect_stale=6,
                               candidates=cands)
            costs = {bq: analysis.delta_attention_cost(
                6, k_tok, d_model, heads, block_q=bq)["time_s"]
                for bq in cands}
            assert got == min(costs, key=costs.get)


class TestValidationAndDiscipline:
    def test_backend_eps_without_cache_raises(self, served):
        cfg, params = served
        rgb = jnp.zeros((1, 64, 64, 3), jnp.float32)
        with pytest.raises(ValueError, match="backend_eps"):
            vit_forward_compact(params, rgb, cfg,
                                backend_eps=jnp.zeros((1,)))

    def test_cache_dtype_mismatch_raises(self, served):
        cfg, params = served
        rgb = jnp.zeros((1, 64, 64, 3), jnp.float32)
        bad = init_backend_cache(cfg, cfg.frontend.n_active, (1,),
                                 dtype=jnp.float32)
        with pytest.raises(ValueError, match="dtype"):
            vit_forward_compact(params, rgb, cfg, backend_cache=bad)

    def test_cache_shape_mismatch_raises(self, served):
        cfg, params = served
        rgb = jnp.zeros((1, 64, 64, 3), jnp.float32)
        bad = init_backend_cache(cfg, cfg.frontend.n_active + 1, (1,),
                                 dtype=cfg.frontend.adc.code_dtype)
        with pytest.raises(ValueError, match="rows"):
            vit_forward_compact(params, rgb, cfg, backend_cache=bad)

    def test_fused_embed_rejects_backend_cache(self, served):
        cfg, params = served
        fused = dataclasses.replace(cfg, quant_embed=True, fused_embed=True)
        rgb = jnp.zeros((1, 64, 64, 3), jnp.float32)
        bc = init_backend_cache(cfg, cfg.frontend.n_active, (1,),
                                dtype=cfg.frontend.adc.code_dtype)
        with pytest.raises(ValueError, match="fused_embed"):
            vit_forward_compact(params, rgb, fused, backend_cache=bc)

    def test_wipe_rows_zeroes_hit_rows_dtype_preserving(self, served):
        cfg, _ = served
        bc = BackendCache(*(
            jnp.ones_like(leaf) if leaf.dtype != jnp.bool_
            else jnp.ones_like(leaf)
            for leaf in init_backend_cache(
                cfg, cfg.frontend.n_active, (3,),
                dtype=cfg.frontend.adc.code_dtype)))
        hit = jnp.array([True, False, True])
        wiped = wipe_rows(bc, hit)
        for before, after in zip(bc, wiped):
            assert after.dtype == before.dtype
            assert not np.asarray(after[0]).any()
            assert not np.asarray(after[2]).any()
            np.testing.assert_array_equal(np.asarray(after[1]),
                                          np.asarray(before[1]))

    def test_saliency_layers_validated(self, served):
        cfg, params = served
        bad = dataclasses.replace(cfg, saliency_layers="first")
        rgb = jnp.zeros((1, 64, 64, 3), jnp.float32)
        with pytest.raises(ValueError, match="saliency_layers"):
            vit_forward_compact(params, rgb, bad)


class TestEngineBackend:
    """SaccadeEngine(backend_delta=True): twin equivalence, per-slot
    reuse state across churn, the governed eps knob — house allclose
    discipline (cross-program oracles, atol=1e-5)."""

    def test_twin_engine_matches_dense_engine(self, served):
        cfg, params = served
        imgs, _ = SceneStream(image=64).batch(3, 2)
        eng_d = SaccadeEngine(cfg, params, capacity=2, temporal=True)
        eng_b = SaccadeEngine(cfg, params, capacity=2, temporal=True,
                              backend_delta=True)
        for e in (eng_d, eng_b):
            e.admit("a")
            e.admit("b")
        for t in range(8):
            od = eng_d.step({"a": imgs[0], "b": imgs[1]})
            ob = eng_b.step({"a": imgs[0], "b": imgs[1]})
            for sid in od:
                np.testing.assert_allclose(od[sid], ob[sid], atol=1e-5)
        assert eng_b.n_traces == 1
        assert np.array_equal(eng_d.gaze("a"), eng_b.gaze("a"))

    def test_static_stream_reaches_zero_backend_macs(self, served):
        cfg, params = served
        # the explore/baseline policy period-2 oscillates the gaze on some
        # scenes, and which ones depends on the host's float rounding:
        # every scene whose selection has converged must be served from
        # the cache, and at least one of the four must converge
        imgs, _ = SceneStream(image=64).batch(0, 4)
        # empty slots must not block the whole-batch skip (act mask)
        eng = SaccadeEngine(cfg, params, capacity=4, temporal=True,
                            backend_delta=True)
        converged = 0
        for i, img in enumerate(imgs):
            sid = f"s{i}"
            eng.admit(sid)
            gazes = []
            for t in range(10):
                eng.step({sid: img})
                gazes.append(np.sort(np.asarray(eng.gaze(sid))))
            if all(np.array_equal(g, gazes[-1]) for g in gazes[-3:]):
                converged += 1
                assert eng.backend_cached(sid)
                assert float(eng.events(sid, "last").backend_macs) == 0.0
            eng.evict(sid)
        assert converged >= 1
        assert eng.n_traces == 1

    def test_churn_wipes_backend_cache_without_retrace(self, served):
        cfg, params = served
        imgs, _ = SceneStream(image=64).batch(0, 2)
        eng = SaccadeEngine(cfg, params, capacity=2, temporal=True,
                            backend_delta=True)
        eng.admit("a")
        eng.admit("b")
        for t in range(3):
            eng.step({"a": imgs[0], "b": imgs[1]})
        assert bool(eng.state.bcache.valid[eng.slot_of("a")])
        eng.evict("a")
        eng.admit("c")
        st = eng.state
        slot = eng.slot_of("c")
        assert not bool(st.bcache.valid[slot])
        assert not np.asarray(st.bcache.feats[slot]).any()
        assert st.bcache.feats.dtype == cfg.frontend.adc.code_dtype
        eng.step({"c": imgs[0], "b": imgs[1]})
        assert eng.n_traces == 1

    def test_held_slot_backend_cache_is_bitwise_frozen(self, served):
        cfg, params = served
        imgs, _ = SceneStream(image=64).batch(0, 2)
        eng = SaccadeEngine(cfg, params, capacity=2, temporal=True,
                            backend_delta=True)
        eng.admit("a")
        eng.admit("b")
        eng.step({"a": imgs[0], "b": imgs[1]})
        before = jax.device_get(eng.state.bcache)
        eng.step({"a": imgs[0]})                 # b holds
        after = jax.device_get(eng.state.bcache)
        for x, y in zip(before, after):
            np.testing.assert_array_equal(np.asarray(x[1]), np.asarray(y[1]))

    def test_governor_eps_knob_engages_and_recovers(self, served):
        cfg, params = served
        imgs, _ = SceneStream(image=64).batch(0, 1)
        spec = gov_mod.GovernorSpec(budget_mw=1e-4, backend_eps=0.05)
        eng = SaccadeEngine(cfg, params, capacity=1, temporal=True,
                            governor=spec, backend_delta=True)
        eng.admit("a")
        for t in range(4):
            eng.step({"a": imgs[0]})
        # starved budget: the backend epsilon tier engages
        assert eng.backend_eps("a") == pytest.approx(0.05)
        # slack budget: it recovers to exact
        eng.set_budget_mw(1e6)
        for t in range(4):
            eng.step({"a": imgs[0]})
        assert eng.backend_eps("a") == 0.0
        assert eng.n_traces == 1                 # data knob, one compile

    def test_governor_backend_eps_requires_backend_delta(self, served):
        cfg, params = served
        spec = gov_mod.GovernorSpec(budget_mw=1.0, backend_eps=0.05)
        with pytest.raises(ValueError, match="backend_delta"):
            SaccadeEngine(cfg, params, capacity=1, temporal=True,
                          governor=spec)

    def test_backend_accessors_raise_when_unbuilt(self, served):
        cfg, params = served
        eng = SaccadeEngine(cfg, params, capacity=1, temporal=True)
        eng.admit("a")
        with pytest.raises(RuntimeError, match="backend_delta"):
            eng.backend_cached("a")
        spec = gov_mod.GovernorSpec(budget_mw=1.0)
        eng_g = SaccadeEngine(cfg, params, capacity=1, temporal=True,
                              governor=spec)
        eng_g.admit("a")
        with pytest.raises(RuntimeError, match="backend_delta"):
            eng_g.backend_eps("a")


class TestStatefulFuzzBackend:
    """Random admit/evict/partial-step churn on a backend-delta engine
    against per-stream dense-backend single-stream loops: arbitrary
    stale patterns (frame pools + frame-rate skew drive arbitrary
    hold/change row mixes) must never diverge past the house tolerance."""

    @pytest.mark.parametrize("seed", [0, 1])
    def test_random_async_churn_backend_vs_dense_oracle(self, served, seed):
        cfg, params = served
        capacity = 3
        eng = SaccadeEngine(cfg, params, capacity=capacity, temporal=True,
                            backend_delta=True)
        boot = jax.jit(make_bootstrap_indices(cfg))
        step1 = jax.jit(make_saccade_step(cfg, temporal=True))
        pool = SceneStream(image=64).batch(7000 + seed, 6)[0]

        rng = np.random.default_rng(500 + seed)
        slots: list = [None] * capacity
        refs: dict = {}              # sid -> [idx, tcache, age]
        next_id = 0
        for op_i in range(30):
            op = rng.choice(["admit", "evict", "step"], p=[0.3, 0.15, 0.55])
            if op == "admit":
                if None not in slots:
                    continue
                sid = f"s{next_id}"
                next_id += 1
                slots[slots.index(None)] = sid
                eng.admit(sid)
                refs[sid] = [None, init_feature_cache(cfg.frontend, (1,)), 0]
            elif op == "evict":
                live = [s for s in slots if s is not None]
                if not live:
                    continue
                sid = live[int(rng.integers(len(live)))]
                eng.evict(sid)
                slots[slots.index(sid)] = None
                del refs[sid]
            else:
                live = [s for s in slots if s is not None]
                fed = [sid for sid in live if rng.random() < 0.7]
                frames = {
                    # repeat frames often (held rows) with occasional
                    # switches (stale rows): arbitrary reuse patterns
                    sid: pool[(slots.index(sid) + refs[sid][2] // 3)
                              % len(pool)]
                    for sid in fed
                }
                out = eng.step(frames)
                for sid in fed:
                    r = jnp.asarray(frames[sid])[None]
                    if refs[sid][0] is None:
                        refs[sid][0] = boot(params, r)
                    logits, refs[sid][0], _, refs[sid][1] = step1(
                        params, r, refs[sid][0], refs[sid][1])
                    np.testing.assert_allclose(
                        out[sid], np.asarray(logits[0]), atol=1e-5,
                        err_msg=f"op {op_i}: stream {sid} diverged")
                    refs[sid][2] += 1
        assert eng.n_traces <= 1
