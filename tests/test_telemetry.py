"""Engine telemetry (DESIGN.md §15): the host-path spans of one tick and
their nesting, the counters at the same boundaries, the fixed-size span
ring, the layer-scope map of the compiled step, and that the scopes leave
the served values bitwise unchanged."""

import contextlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.frontend import FrontendConfig
from repro.core.projection import PatchSpec
from repro.core.temporal import TemporalSpec
from repro.data.pipeline import SceneStream
from repro.models.vit import ViTConfig, init_vit
from repro.serve import telemetry
from repro.serve.engine import SaccadeEngine

KEY = jax.random.PRNGKey(0)
H = W = 64


def _cfg():
    fcfg = FrontendConfig(
        image_h=H, image_w=W,
        patch=PatchSpec(patch_h=16, patch_w=16, n_vectors=32),
        active_fraction=0.25,
        temporal=TemporalSpec(delta_threshold=1e-3),
    )
    return ViTConfig(frontend=fcfg, n_layers=1, d_model=32, n_heads=2,
                     d_ff=64)


@pytest.fixture(scope="module")
def served():
    cfg = _cfg()
    return cfg, init_vit(KEY, cfg)


@pytest.fixture(scope="module")
def frames():
    rgb, _ = SceneStream(image=H).batch(0, 3)
    return [np.asarray(f) for f in rgb]


def _engine(served, **kw):
    cfg, params = served
    return SaccadeEngine(cfg, params, capacity=4, temporal=True,
                         backend_delta=True, **kw)


def _new_rows(before: dict) -> dict:
    """The span rows written since the snapshot ``before``."""
    snap = telemetry.snapshot()
    n = snap["written"] - before["written"]
    return {k: v[len(v) - n:] for k, v in snap["spans"].items()}


def _counter_delta(before: dict) -> dict:
    after = telemetry.snapshot()["counters"]
    return {k: after[k] - before["counters"][k] for k in after}


def test_one_step_records_one_nested_tick(served, frames):
    eng = _engine(served)
    eng.admit("a")
    eng.admit("b")
    eng.state                                   # flush the admits first
    before = telemetry.snapshot()
    handle = eng.step({"a": frames[0], "b": frames[1]}, block=False)
    handle.result()
    handle.result()                             # idempotent: no second span
    rows = _new_rows(before)
    assert sorted(rows["name"].tolist()) == sorted(
        ["engine.step", "engine.stage", "engine.upload", "engine.dispatch",
         "engine.result"])
    by = {str(n): i for i, n in enumerate(rows["name"])}
    step = by["engine.step"]
    for child in ("engine.stage", "engine.upload", "engine.dispatch"):
        i = by[child]
        assert rows["parent"][i] == "engine.step"
        assert rows["start_ns"][step] <= rows["start_ns"][i]
        assert rows["end_ns"][i] <= rows["end_ns"][step]
    assert rows["end_ns"][by["engine.stage"]] <= \
        rows["start_ns"][by["engine.upload"]] <= \
        rows["end_ns"][by["engine.upload"]] <= \
        rows["start_ns"][by["engine.dispatch"]]
    res = by["engine.result"]
    assert rows["parent"][res] == "" and rows["parent"][step] == ""
    assert len(set(rows["tick"].tolist())) == 1
    assert handle.tick == rows["tick"][res]
    assert rows["start_ns"][res] >= rows["end_ns"][step]


def test_h2d_bytes_and_frames_per_step(served, frames):
    eng = _engine(served)
    for sid in "abc":
        eng.admit(sid)
    eng.state
    for fed in (["a", "b", "c"], ["b"], ["a", "c"]):
        before = telemetry.snapshot()
        eng.step({s: frames[i] for i, s in enumerate(fed)})
        f = len(fed)
        delta = _counter_delta(before)
        assert delta["h2d_bytes"] == f * H * W * 3 * 4 + 4 * f
        assert delta["frames_fed"] == f and delta["ticks"] == 1
        assert delta["churn_flushes"] == delta["churn_rows"] == 0
        rows = _new_rows(before)
        counts = dict(zip(rows["name"].tolist(), rows["count"].tolist()))
        assert counts["engine.stage"] == f
        assert counts["engine.upload"] == delta["h2d_bytes"]


def test_coalesced_churn_is_one_flush(served, frames):
    eng = _engine(served)
    eng.admit("a")
    eng.admit("b")
    eng.step({"a": frames[0], "b": frames[1]})
    before = telemetry.snapshot()
    eng.evict("a")
    eng.admit("c")                  # a's slot: last op wins, one row
    eng.admit("d")
    eng.evict("b")
    eng.step({"c": frames[2]})
    delta = _counter_delta(before)
    assert delta["churn_flushes"] == 1 and delta["churn_rows"] == 3
    rows = _new_rows(before)
    flush = rows["name"] == "engine.churn_flush"
    assert flush.sum() == 1 and rows["parent"][flush][0] == "engine.step"
    # a flush forced by a state read belongs to no span, and to the tick
    # the next step takes
    before = telemetry.snapshot()
    eng.evict("d")
    eng.state
    rows = _new_rows(before)
    assert rows["name"].tolist() == ["engine.churn_flush"]
    assert rows["parent"][0] == "" and rows["count"][0] == 1
    eng.step({"c": frames[0]})
    rows = _new_rows(before)
    assert len(set(rows["tick"].tolist())) == 1
    assert _counter_delta(before)["churn_rows"] == 1


def test_ring_wraps_without_growing():
    tel = telemetry.Telemetry(rows=8)
    ring = tel._ring
    for _ in range(5):
        tick = tel.new_tick()
        with tel.span(telemetry.STEP, tick, count=1):
            with tel.span(telemetry.STAGE, count=2):
                pass
            with tel.span(telemetry.UPLOAD, count=10):
                pass
    snap = tel.snapshot()
    assert tel._ring is ring and len(ring) == 8 * 6
    assert snap["written"] == 15 and snap["dropped"] == 7
    assert len(snap["spans"]["name"]) == 8
    # oldest kept first: the last 8 rows written, in order
    assert snap["spans"]["tick"].tolist() == [2, 2, 3, 3, 3, 4, 4, 4]
    assert snap["spans"]["name"].tolist()[-3:] == [
        "engine.stage", "engine.upload", "engine.step"]
    # the counters keep every span, also those the ring lost
    assert snap["counters"]["ticks"] == 5
    assert snap["counters"]["frames_fed"] == 10
    assert snap["counters"]["h2d_bytes"] == 50
    # a span that raises is kept, and its count is not
    with pytest.raises(RuntimeError):
        with tel.span(telemetry.STAGE, tel.new_tick(), count=3):
            raise RuntimeError
    snap = tel.snapshot()
    assert snap["counters"]["frames_fed"] == 10
    assert snap["spans"]["count"][-1] == 0


def test_scope_map_names_every_instruction(served, frames):
    eng = _engine(served)
    eng.admit("a")
    text = eng.compile_step().as_text()
    scopes = telemetry.snapshot()["scopes"]["jit_counted"]
    named = {}
    for line in text.splitlines():
        m = re.match(r'\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*op_name="([^"]*)"',
                     line)
        if m:
            named[m.group(1)] = m.group(2).split("/")
    assert named and set(named) <= set(scopes)
    for name, parts in named.items():
        inner = [p for p in parts if p in telemetry.LAYERS]
        assert scopes[name] == (inner[-1] if inner else "unscoped"), name
    assert set(scopes.values()) <= set(telemetry.LAYERS) | {"unscoped"}
    assert {"sensor", "frontend", "encoder", "policy"} <= set(
        scopes.values())
    assert eng.n_traces == 1



def test_sensor_ops_map_to_the_sensor_scope(served, frames):
    """Every frame-sized instruction of the compiled step (the optics
    filter, the mosaic, the compiler's copies around them) is the sensor
    emulation's, maps to ``sensor``, and is elementwise: no contraction
    and no transpose of a frame."""
    eng = _engine(served)
    eng.admit("a")
    text = eng.compile_step().as_text()
    scopes = telemetry.snapshot()["scopes"]["jit_counted"]
    entry = text[text.index("\nENTRY"):]
    instr = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+)\s+([\w\-]+)\(")
    frame = re.compile(rf"f32\[4,{H},{W}(,3)?\]")
    found = {}
    for line in text.splitlines():
        m = instr.match(line)
        if m and frame.match(m.group(2)) and not (
                m.group(3) == "parameter" and line in entry):
            found[m.group(1)] = m.group(3)
    assert found
    assert {scopes[name] for name in found} == {"sensor"}
    assert not {"dot", "convolution", "transpose"} & set(found.values())
    assert eng.n_traces == 1

HLO = """HloModule jit_counted, is_scheduled=true

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(counted)/encoder/cond/branch_1_fun/embed/mul"}
}

ENTRY %main.2 (w.1: f32[4], x.1: f32[4]) -> (f32[4], f32[4]) {
  %w.1 = f32[4]{0} parameter(0), metadata={op_name="params"}
  %x.1 = f32[4]{0} parameter(1), metadata={op_name="frames"}
  %copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%w.1)
  %copy-done.1 = f32[4]{0} copy-done(%copy-start.1)
  %sin.1 = f32[4]{0} sine(%x.1), metadata={op_name="jit(counted)/sensor/sin"}
  %fusion.1 = f32[4]{0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(counted)/encoder/cond/branch_1_fun/embed/mul"}
  %copy.2 = f32[4]{0} copy(%sin.1)
  %and.1 = f32[4]{0} add(%copy.2, %fusion.1), metadata={op_name="jit(counted)/and"}
  %copy.3 = f32[4]{0} copy(%and.1)
  ROOT %tuple.1 = (f32[4]{0}, f32[4]{0}) tuple(%copy.2, %copy.3)
}
"""


def test_scope_map_gives_compiler_instructions_a_neighbours_layer():
    scopes = telemetry.Telemetry().record_scopes(HLO)
    assert scopes["mul.1"] == scopes["fusion.1"] == "embed"
    assert scopes["sin.1"] == "sensor"
    assert scopes["and.1"] == "unscoped" and scopes["w.1"] == "unscoped"
    # a prefetch takes the layer of what it feeds
    assert scopes["copy-start.1"] == scopes["copy-done.1"] == "embed"
    # a copy whose consumers name no layer takes its producer's
    assert scopes["copy.2"] == "sensor"
    # nothing near names a layer: it feeds only the result tuple, and an
    # instruction with no layer feeds it
    assert scopes["copy.3"] == "unscoped"


@pytest.fixture
def no_compile_cache():
    """A persistent compile cache that an earlier test of the process
    turned on keys a program without its ``op_name`` metadata, so it can
    hand a step traced without scopes the executable of the same step
    traced with them: compile afresh here, and restore it after."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def test_scopes_leave_the_step_bitwise_unchanged(served, frames,
                                                 monkeypatch,
                                                 no_compile_cache):
    """An engine whose step is traced without the layer scopes is the
    oracle: logits and every state leaf equal it bitwise, tick by tick,
    through churn."""
    eng = _engine(served)
    twin = _engine(served)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        twin.admit("warm")
        twin.step({"warm": frames[0]})          # traces without scopes
        plain = twin._step_fn.lower(
            twin.params, twin._frames_dev, jnp.asarray(twin._fed),
            twin._state).compile().as_text()
    twin.evict("warm")
    eng.admit("warm")
    eng.step({"warm": frames[0]})
    eng.evict("warm")
    assert not any(f"/{layer}/" in plain for layer in telemetry.LAYERS)
    for e in (eng, twin):
        e.admit("a")
        e.admit("b")
    for t in range(4):
        fed = {"a": frames[t % 3], "b": frames[(t + 1) % 3]}
        if t == 2:
            fed = {"b": frames[0]}
        out, ref = eng.step(fed), twin.step(fed)
        for sid in ref:
            np.testing.assert_array_equal(out[sid], ref[sid])
        for x, y in zip(jax.tree.leaves(eng.state),
                        jax.tree.leaves(twin.state)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert eng.n_traces == twin.n_traces == 1
