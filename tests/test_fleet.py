"""Fleet coordinator (DESIGN.md §12): per-host engines, priority-class
admit queues, fleet->host->slot budget hierarchy, async routing."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.frontend import FrontendConfig
from repro.core.projection import PatchSpec
from repro.core.temporal import TemporalSpec
from repro.data.pipeline import SceneStream
from repro.models.vit import ViTConfig, init_vit
from repro.serve.engine import SaccadeEngine
from repro.serve.fleet import SaccadeFleet, make_fleet_meshes
from repro.serve.governor import GovernorSpec
from repro.serve.serve_step import make_bootstrap_indices, make_saccade_step

KEY = jax.random.PRNGKey(0)


def _cfg(temporal=False):
    fcfg = FrontendConfig(
        image_h=64, image_w=64,
        patch=PatchSpec(patch_h=16, patch_w=16, n_vectors=32),
        active_fraction=0.25,
        temporal=TemporalSpec(delta_threshold=1e-4) if temporal
        else TemporalSpec(),
    )
    return ViTConfig(frontend=fcfg, n_layers=1, d_model=32, n_heads=2, d_ff=64)


@pytest.fixture(scope="module")
def served():
    cfg = _cfg()
    return cfg, init_vit(KEY, cfg)


class TestAdmission:
    def test_priority_classes_drain_highest_first(self, served):
        """With fewer free slots than queued requests, realtime admits
        before standard before background — FIFO within a class."""
        cfg, params = served
        fl = SaccadeFleet(cfg, params, n_hosts=1, capacity=2)
        fl.submit("bg", "background")
        fl.submit("rt", "realtime")
        fl.submit("std", "standard")
        admitted = fl.drain()
        assert admitted == ["rt", "std"]         # capacity 2: bg waits
        assert fl.queued == 1
        fl.evict("rt")
        assert fl.drain() == ["bg"]
        assert fl.queued == 0

    def test_submit_validation_and_cancel(self, served):
        cfg, params = served
        fl = SaccadeFleet(cfg, params, n_hosts=1, capacity=2)
        fl.submit("a")
        with pytest.raises(ValueError, match="already submitted"):
            fl.submit("a")
        with pytest.raises(ValueError, match="priority class"):
            fl.submit("b", "vip")
        fl.evict("a")                            # cancels the queued request
        assert fl.queued == 0
        with pytest.raises(KeyError):
            fl.evict("a")

    def test_least_loaded_host_placement(self, served):
        cfg, params = served
        fl = SaccadeFleet(cfg, params, n_hosts=2, capacity=2)
        hosts = [fl.submit(f"s{i}") for i in range(4)]
        assert sorted(hosts) == [0, 0, 1, 1]     # spread, not piled
        fl.drain()
        assert fl.free_slots == 0
        assert {fl.host_of(f"s{i}") for i in range(4)} == {0, 1}


class TestServing:
    def test_streams_match_dedicated_loops_across_hosts(self, served):
        """Every stream, whatever host it landed on and whatever rate it
        is fed at, matches its own dedicated batch-1 loop — the fleet
        layer adds routing, never semantics. One compile per engine."""
        cfg, params = served
        fl = SaccadeFleet(cfg, params, n_hosts=2, capacity=2)
        for i in range(3):
            fl.submit(f"s{i}")
        stream = SceneStream(image=64)
        boot = jax.jit(make_bootstrap_indices(cfg))
        step1 = jax.jit(make_saccade_step(cfg))
        refs = {f"s{i}": None for i in range(3)}
        for t in range(4):
            rgb, _ = stream.batch(t, 3)
            frames = {f"s{i}": rgb[i] for i in range(3) if (t + i) % 2 == 0}
            out = fl.step(frames)
            assert set(out) == set(frames)
            for i in range(3):
                sid = f"s{i}"
                if sid not in frames:
                    continue
                r = jnp.asarray(rgb[i:i + 1])
                if refs[sid] is None:
                    refs[sid] = boot(params, r)
                logits, refs[sid], _ = step1(params, r, refs[sid])
                np.testing.assert_allclose(
                    out[sid], np.asarray(logits[0]), atol=1e-5)
        assert fl.n_traces == [1, 1]

    def test_only_fed_hosts_dispatch(self, served):
        cfg, params = served
        fl = SaccadeFleet(cfg, params, n_hosts=2, capacity=1)
        fl.submit("a")
        fl.submit("b")
        fl.drain()
        ha, hb = fl.host_of("a"), fl.host_of("b")
        assert ha != hb
        stream = SceneStream(image=64)
        rgb, _ = stream.batch(0, 1)
        fl.step({"a": rgb[0]})                   # only a's host runs
        assert fl.engines[ha].n_traces == 1
        assert fl.engines[hb].n_traces == 0


class TestBudgetHierarchy:
    def test_fleet_budget_splits_host_then_slot(self):
        """fleet -> host by admitted priority mass, host -> slot by
        stream priority: the slot shares on each host sum to the host
        share, and the host shares sum to the fleet budget."""
        cfg = _cfg(temporal=True)
        params = init_vit(KEY, cfg)
        gov = GovernorSpec(budget_mw=1.0)
        fl = SaccadeFleet(cfg, params, n_hosts=2, capacity=2,
                          temporal=True, governor=gov)
        fl.submit("rt", "realtime")              # weight 4, host 0
        fl.submit("bg", "background")            # weight 0.25, host 1
        fl.submit("std", "standard")             # weight 1
        fl.drain()
        masses = [sum(e._priority[s] for s in e.stream_ids)
                  for e in fl.engines]
        total = sum(masses)
        host_shares = []
        for eng, mass in zip(fl.engines, masses):
            b = np.asarray(eng.state.controls.budget_mw)
            assert b.sum() == pytest.approx(eng.budget_mw, rel=1e-5)
            assert eng.budget_mw == pytest.approx(
                gov.budget_mw * mass / total, rel=1e-5)
            host_shares.append(b.sum())
        assert sum(host_shares) == pytest.approx(gov.budget_mw, rel=1e-5)

    def test_slack_fleet_budget_is_bitwise_noop(self):
        """PR-5 contract lifted to the fleet: a slack fleet budget leaves
        every stream bitwise identical to an ungoverned fleet of the same
        layout — each host's slack share is itself slack. Both sides run
        capacity-1 host engines: engines of another capacity are other
        programs, which XLA may fuse 1 ulp apart (DESIGN.md §14)."""
        cfg = _cfg(temporal=True)
        params = init_vit(KEY, cfg)
        fl = SaccadeFleet(cfg, params, n_hosts=2, capacity=1, temporal=True,
                          governor=GovernorSpec(budget_mw=1e4))
        plain = SaccadeFleet(cfg, params, n_hosts=2, capacity=1,
                             temporal=True)
        # and the fleet serves what one ungoverned engine holding both
        # streams serves, to XLA's cross-program drift: at most 5.96e-7
        # (tick 1, stream a, logit 2 of magnitude 1.15) on an AVX-512 x86
        # CPU
        single = SaccadeEngine(cfg, params, capacity=2, temporal=True)
        for f in (fl, plain):
            f.submit("a", "realtime")
            f.submit("b", "background")
        single.admit("a")
        single.admit("b")
        stream = SceneStream(image=64)
        for t in range(4):
            rgb, _ = stream.batch(t % 2, 2)
            frames = {"a": rgb[0], "b": rgb[1]}
            og = fl.step(frames)
            op = plain.step(frames)
            os_ = single.step(frames)
            for sid in frames:
                np.testing.assert_array_equal(og[sid], op[sid])
                np.testing.assert_allclose(og[sid], os_[sid], rtol=0,
                                           atol=1e-6)


class TestMeshes:
    def test_make_fleet_meshes_partitions_devices(self):
        meshes = make_fleet_meshes(1)
        assert len(meshes) == 1
        assert meshes[0].devices.size == len(jax.devices())
        with pytest.raises(ValueError, match="devices"):
            make_fleet_meshes(len(jax.devices()) + 1)
        with pytest.raises(ValueError, match="n_hosts"):
            make_fleet_meshes(0)
