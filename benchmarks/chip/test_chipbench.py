"""CPU tests of the on-chip benchmark's yardstick and harness.

They cover the trace reduction on a hand-built trace, the operation and
byte counts against the program's own counters, discovery of a config,
traffic mix, check and metric added as files, a second backend added as
files only, whole runs of a tiny cell with the device check skipped
(kernels in interpret mode), the open loop's churn and failure accounting
on a simulated clock, and that the comparison fails the lower-precision
control, a broken timed path and a perturbed output.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import check, costs, harness, spec, trace  # noqa: E402

FAKE_CHIP = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
TINY = dict(name="tiny", frame_h=64, frame_w=96, patch=16, n_vectors=16,
            n_layers=2, d_model=32, n_heads=2, d_ff=64)


# ---------------------------------------------------------------- trace
def _trace():
    """Two ticks; device ops: a kernel, a fusion, the kernel again."""
    ops = [(1.0, 1.2, "ip2_ragged_pallas.1"), (1.1, 1.3, "fusion.7"),
           (2.0, 2.5, "ip2_ragged_pallas.1")]
    spans = [("trace_window", 0.0, 4.0),
             ("stage_dispatch", 0.9, 1.0), ("fetch", 1.0, 1.6),
             ("wait_frames", 1.6, 1.9),
             ("stage_dispatch", 1.9, 2.0), ("fetch", 2.0, 2.6)]
    return {"ops": [ops], "spans": spans}


def test_trace_busy_union_and_idle_share():
    tr = _trace()
    # union of [1.0, 1.3] and [2.0, 2.5]
    assert trace.busy_s(tr) == pytest.approx(0.8)
    assert trace.union(tr["ops"][0], 0.0, 4.0) == [[1.0, 1.3], [2.0, 2.5]]


def test_idle_share_per_tick():
    from chipbench import readers

    tr = _trace()
    # step programs of 0.25 s and 0.35 s and a scatter of 0.1 s in the
    # window (one step half outside it); untraced ticks (start, call,
    # dispatched, done, fed) take 1.0 s and 0.6 s from call to results
    tr["modules"] = [[(0.5, 0.6, "jit_scatter"), (1.0, 1.25, "jit_counted"),
                      (2.0, 2.35, "jit_counted"), (3.9, 4.5, "jit_counted")]]
    assert trace.module_s(tr, "jit_counted") == pytest.approx(0.3)
    ctx = types.SimpleNamespace(
        trace=tr, device={"busy_s": trace.busy_s(tr)}, t_cut=10.0,
        rec=types.SimpleNamespace(ticks=[(0.0, 0.0, 0.1, 1.0, 3),
                                         (2.0, 2.0, 2.1, 2.6, 3),
                                         (11.0, 11.0, 11.1, 19.0, 3)]))
    assert readers.idle_share(ctx) == pytest.approx(1.0 - 0.4 / 0.8)
    del tr["modules"][0][0]             # no scatter traced: the step alone
    assert readers.idle_share(ctx) == pytest.approx(1.0 - 0.3 / 0.8)
    assert readers.host_dispatch_ms(ctx) == pytest.approx(100.0)


def test_trace_kernel_time_and_names():
    tr = _trace()
    hlo = ('  %ip2_ragged_pallas.1 = s8[128,256]{1,0} custom-call(%a), '
           'custom_call_target="tpu_custom_call", x\n'
           '  %fusion.7 = f32[8]{0} fusion(%b), kind=kLoop\n')
    kernels = trace.kernel_map(hlo)
    assert kernels == {"ip2_ragged_pallas.1": "ip2_ragged_pallas"}
    secs, calls = trace.kernel_s(tr, kernels, "ip2_ragged_pallas")
    assert (secs, calls) == (pytest.approx(0.7), 2)
    fams = dict(trace.device_ops(tr, kernels))
    assert fams == {"ip2_ragged_pallas": pytest.approx(0.7),
                    "fusion": pytest.approx(0.2)}


def test_trace_gap_attribution():
    gaps = dict(trace.idle_gaps(_trace()))
    # idle [0, 1.0] and [2.5, 4.0] have no host phase at their middles;
    # the middle of [1.3, 2.0] lies in wait_frames
    assert gaps == {"other": pytest.approx(1.0 + 1.5),
                    "wait_frames": pytest.approx(0.7)}


# ---------------------------------------------------------------- counts
@pytest.mark.parametrize("conf_name", ["ip2-vit", "ip2-2mpix"])
def test_backend_macs_match_program_counter(conf_name):
    from repro.core.power import dense_backend_macs

    c = spec.config(conf_name)
    k = check_k(c)
    ours = spec.system(c["reference"]).backend_macs(
        k, c["n_layers"], c["n_vectors"], c["d_model"], c["d_ff"],
        c["n_classes"])
    assert ours == dense_backend_macs(k, c["n_layers"], c["n_vectors"],
                                      c["d_model"], c["d_ff"], c["n_classes"])


@pytest.mark.parametrize("conf_name", ["ip2-vit", "ip2-2mpix"])
def test_projection_counts_match_program_cost_model(conf_name):
    from repro.roofline.analysis import megakernel_cost

    c = spec.config(conf_name)
    k, n2, m = check_k(c), c["patch"] ** 2, c["n_vectors"]
    counts = [k, k // 2, 0, 3]
    # without padding or banking the program's model is the least count
    least = megakernel_cost(counts, k, n2, m, block_r=1, block_m=1,
                            block_k=1)
    ours = costs.projection_min(sum(counts), n2, m, 0)
    assert ours["flops"] == least["flops"]
    # the kernel's own traffic (weights per bank, padding) is never less
    assert ours["bytes"] <= megakernel_cost(counts, k, n2, m)["bytes"]


def check_k(c):
    return spec.reference(c["reference"]).sizes(c)["k"]


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        costs.peaks("cpu")


# ---------------------------------------------------------------- runs
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A copy of the benchmark with a tiny config, two tiny mixes, their
    checks and a new metric, all added as files; BENCHMARK.json names the
    tiny cells."""
    root = str(tmp_path_factory.mktemp("bench"))
    here = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*.py"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    conf = {**spec.config("ip2-vit"), **TINY}
    _dump(os.path.join(here, "configs", "tiny.json"), conf)
    _dump(os.path.join(here, "traffic", "tiny_open.json"),
          {**spec.traffic("surveil"), "streams": 4, "scenes": 8,
           "intruder_px": 12, "intruder_rate_per_s": 1.0, "preroll_s": 0.5,
           "check_streams": 3, "check_block": 2, "check_t_bucket": 16})
    _dump(os.path.join(here, "traffic", "tiny_closed.json"),
          {**spec.traffic("saturate"), "streams": 3, "scenes": 4,
           "check_streams": 3, "check_block": 2, "check_t_bucket": 16})
    real = spec.limits("vit256.surveil")
    for c in ("tiny.open", "tiny.closed"):
        _dump(os.path.join(here, "checks", f"{c}.json"),
              {**real, "min_frames": 5})
    with open(os.path.join(here, "metrics", "ticks_served.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx.rec.ticks)\n")
    bench = spec.benchmark(ROOT)
    bench["workloads"] = [
        {"name": "tiny.open", "config": "tiny", "traffic": "tiny_open",
         "chips": 1, "why": "tiny open loop"},
        {"name": "tiny.closed", "config": "tiny", "traffic": "tiny_closed",
         "chips": 1, "why": "tiny closed loop"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.open" if w == "vit256.surveil"
                              else "tiny.closed" for w in m["workloads"]]
    bench["per_layer"].append(
        {"name": "ticks_served", "unit": "ticks", "better": "higher",
         "source": "host_clock", "layer": "engine host path",
         "moves": "frames_per_s"})
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def _run(root, cell, trace_on=0, control=0, seconds=1.5):
    here = os.path.join(root, "benchmarks", "chip")
    args = types.SimpleNamespace(workload=cell, seed=2**31 + 977,
                                 seconds=seconds, trace=trace_on,
                                 streams=None, control=control)
    return harness.run(args, root, here, device_check=lambda n: dict(FAKE_CHIP))


def test_files_added_are_discovered(tiny_root):
    here = os.path.join(tiny_root, "benchmarks", "chip")
    bench = spec.benchmark(tiny_root)
    assert spec.config("tiny", here)["d_model"] == 32
    assert spec.traffic("tiny_open", here)["streams"] == 4
    assert spec.limits("tiny.open", here)["min_frames"] == 5
    assert spec.reader("ticks_served", here)(
        types.SimpleNamespace(rec=types.SimpleNamespace(ticks=[1, 2]))) == 2
    names = {m["name"] for m in spec.metrics_for(bench, "tiny.closed", True)}
    assert "ticks_served" in names and "step_mfu.sat" in names
    assert "ticks_served" not in {
        m["name"] for m in spec.metrics_for(bench, "tiny.open", True)}
    assert {m["name"] for m in spec.metrics_for(bench, "tiny.open", False)} \
        == {"frame_p50_ms", "frame_p95_ms", "setup_s"}


@pytest.mark.parametrize("cell", ["tiny.open", "tiny.closed"])
def test_tiny_run_is_correct(tiny_root, cell):
    res = _run(tiny_root, cell)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = ({"frame_p50_ms", "frame_p95_ms"} if cell == "tiny.open"
            else {"frames_per_s"}) | {"setup_s"}
    assert set(res["metrics"]) == want


@pytest.mark.parametrize("cell", ["vit256.surveil", "mpix2.saturate"])
def test_control_fails_at_ip2_vit_widths(cell):
    """The reference computed in three bfloat16 passes, put in the
    program's place, fails the cell's limits. At the ip2-vit widths
    (a test run cannot hold 2 Mpix frames), two cameras that see a new
    scene every frame, each gaze the float32 reference's own choice."""
    import jax
    import jax.numpy as jnp

    from chipbench import model, scenes

    conf = spec.config("ip2-vit")
    ref = spec.reference(conf["reference"])
    system = spec.system(conf["reference"])
    k = ref.sizes(conf)["k"]
    w = system.reference_weights(system.make_weights(conf,
                                                     model.seed_key(5)))
    pool = scenes.scene_pool(5, 4, conf["frame_h"], conf["frame_w"])
    b, t_len = 2, 16
    fed = jnp.ones((b,), bool)
    zero_box = jnp.zeros((b, 3), jnp.int32)
    colour = jnp.zeros((b, 3), jnp.float32)
    step = jax.jit(lambda st, rgb, g: ref.frame_step(
        conf, w, st, rgb, g, fed, "float32"))
    energy = jax.jit(lambda rgb: ref.patch_energy(
        ref.sensor(conf, rgb, w["a_rgb"], "float32")[0]))
    st = ref.init_state(conf, b)
    xs = {"scene": [], "gaze": []}
    for t in range(t_len):
        scene = jnp.asarray([(i + t) % 4 for i in range(b)], jnp.int32)
        rgb = ref.frames_of(jnp.asarray(pool), scene, zero_box, colour)
        gaze = ref.topk(energy(rgb) if t == 0 else st["scores"], k)
        st, _, _ = step(st, rgb, gaze)
        xs["scene"].append(scene)
        xs["gaze"].append(gaze)
    xs = {"scene": np.stack(xs["scene"]), "gaze": np.stack(xs["gaze"]),
          "box": np.zeros((t_len, b, 3), np.int32),
          "color": np.zeros((t_len, b, 3), np.float32),
          "fed": np.ones((t_len, b), bool)}
    hi, gaps = check.replay(ref, conf, w, pool, xs, "float32", b)
    lo, lo_gaps = check.replay(ref, conf, w, pool, xs, "bf16x3", b)
    assert system.compare(hi, hi, gaps, xs["fed"])["gaze_gap_max"] == 0.0
    control = system.compare(lo, hi, lo_gaps, xs["fed"])
    ok, _ = check.verdict(control, {**spec.limits(cell), "min_frames": 1},
                          system.NUMBERS)
    assert not ok, control


def test_tiny_traced_run_reports_per_layer(tiny_root):
    res = _run(tiny_root, "tiny.closed", trace_on=1)
    assert res["correct"]
    assert "ticks_served" in res["metrics"]
    assert "host_dispatch_ms.sat" in res["metrics"]
    # no device plane on the CPU: nothing to read, so no kernel share
    assert "ragged_proj_roofline" not in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _broken(monkeypatch, fault):
    """Break the engine's step underneath the harness."""
    import jax.numpy as jnp

    from repro.serve import engine as eng_mod

    real = eng_mod.make_engine_step

    def make(*a, **kw):
        step = real(*a, **kw)

        def bad(params, frames, fed, state):
            logits, new = step(params, frames, fed, state)
            if fault == "state_unchanged":
                return logits, state
            if fault == "half_batch":
                keep = jnp.arange(logits.shape[0]) % 2 == 0
                return jnp.where(keep[:, None], logits, 0.0), new
            if fault == "answer_altered":
                return logits.at[:, 0].add(0.01), new
            if fault == "gaze_altered":
                return logits, new._replace(
                    indices=jnp.roll(new.indices, 1, axis=0))
            raise ValueError(fault)

        return bad

    monkeypatch.setattr(eng_mod, "make_engine_step", make)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered", "gaze_altered"])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault):
    _broken(monkeypatch, fault)
    res = _run(tiny_root, "tiny.open", seconds=1.0)
    assert not res["correct"], res["check"]


PROBS_SYSTEM = """
\"\"\"A second backend: the ip2_saccade engine, whose result gives per frame
its logits and its class probabilities (plus ``OFFSET``).\"\"\"

import math
import os
import types

import numpy as np

from chipbench import spec

base = spec.system("ip2_saccade", os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
make_weights, reference_weights = base.make_weights, base.reference_weights
telemetry, frame_ops = base.telemetry, base.frame_ops
head_macs = base.head_macs
NUMBERS = ("logits_gap_max", "probs_gap_max", "gaze_gap_max")
OFFSET = {offset}


def check_config(conf):
    if conf.get("serve_probs") is not True:
        raise SystemExit("probs: serve_probs must be true")
    base.check_config({{k: v for k, v in conf.items() if k != "serve_probs"}})


def softmax(x):
    e = np.exp(x - np.max(x, axis=-1, keepdims=True))
    return e / np.sum(e, axis=-1, keepdims=True)


class Engine:
    def __init__(self, eng):
        self.eng = eng

    def __getattr__(self, name):
        return getattr(self.eng, name)

    def step(self, frames, block=False):
        handle = self.eng.step(frames, block=block)
        return types.SimpleNamespace(result=lambda: {{
            sid: {{"logits": lg, "probs": softmax(lg) + OFFSET}}
            for sid, lg in handle.result().items()}})


def make_engine(conf, params, capacity, mesh=None):
    return Engine(base.make_engine(conf, params, capacity, mesh))


def compare(served, ref, gaps, mask):
    if not mask.any():
        return {{**dict.fromkeys(NUMBERS, math.inf), "frames": 0}}
    gap = lambda k: float(np.max(np.abs(served[k] - ref[k])[mask]))
    return {{"logits_gap_max": gap("logits"), "probs_gap_max": gap("probs"),
            "gaze_gap_max": float(np.max(gaps[mask])),
            "frames": int(mask.sum())}}
"""

PROBS_REFERENCE = """
\"\"\"The ip2_saccade reference, with each frame's class probabilities.\"\"\"

import os

import jax

from chipbench import spec

base = spec.reference("ip2_saccade", os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sizes = base.sizes


def replay(conf, w, scenes, xs, precision):
    logits, gaps = base.replay(conf, w, scenes, xs, precision)
    return {"logits": logits, "probs": jax.nn.softmax(logits, axis=-1)}, gaps
"""


@pytest.fixture(scope="module")
def probs_root(tiny_root, tmp_path_factory):
    """A copy of the tiny benchmark with a second backend added as files
    only: ``systems/probs.py`` and ``references/probs.py`` (and the same
    system with its probabilities perturbed by 0.01, ``probs_off``), a
    config with a key ``ip2_saccade`` does not implement, checks files
    with the system's own compared numbers, and a cell for each."""
    root = str(tmp_path_factory.mktemp("probs")) + "/bench"
    shutil.copytree(tiny_root, root, symlinks=True)
    here = os.path.join(root, "benchmarks", "chip")
    bench = spec.benchmark(root)
    for name, offset in (("probs", 0.0), ("probs_off", 0.01)):
        with open(os.path.join(here, "systems", f"{name}.py"), "w") as f:
            f.write(PROBS_SYSTEM.format(offset=offset))
        with open(os.path.join(here, "references", f"{name}.py"), "w") as f:
            f.write(PROBS_REFERENCE)
        _dump(os.path.join(here, "configs", f"tiny_{name}.json"),
              {**spec.config("tiny", here), "serve_probs": True,
               "reference": name})
        _dump(os.path.join(here, "checks", f"tiny.{name}.json"),
              {"logits_gap_max": 7e-3, "probs_gap_max": 1e-3,
               "gaze_gap_max": 5e-5, "min_frames": 5})
        bench["workloads"].append(
            {"name": f"tiny.{name}", "config": f"tiny_{name}",
             "traffic": "tiny_open", "chips": 1, "why": "a second backend"})
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.mark.parametrize("case", ["served", "perturbed", "under_ip2_saccade"])
def test_new_backend_is_added_as_files_only(probs_root, case):
    """A backend whose served output is a pytree runs a tiny cell
    ``correct`` on its own compared numbers; the same backend with one
    output leaf perturbed does not; and its config, which asks for a key
    ``ip2_saccade`` does not implement, is still refused under
    ``ip2_saccade`` before the device is touched."""
    if case == "under_ip2_saccade":
        here = os.path.join(probs_root, "benchmarks", "chip")
        conf = spec.config("tiny_probs", here)
        _dump(os.path.join(here, "configs", "tiny_probs.json"),
              {**conf, "reference": "ip2_saccade"})
        touched = []
        args = types.SimpleNamespace(workload="tiny.probs", seed=3,
                                     seconds=1.0, trace=0, streams=None,
                                     control=0)
        try:
            with pytest.raises(SystemExit):
                harness.run(args, probs_root, here,
                            device_check=lambda n: touched.append(n))
        finally:
            _dump(os.path.join(here, "configs", "tiny_probs.json"), conf)
        assert touched == []
        return
    res = _run(probs_root, "tiny.probs" if case == "served"
               else "tiny.probs_off", seconds=1.0)
    assert list(res["check"]) == ["logits_gap_max", "probs_gap_max",
                                  "gaze_gap_max", "frames"]
    assert res["check"]["frames"]["value"] >= 5
    assert res["correct"] == (case == "served"), res["check"]
    assert res["check"]["logits_gap_max"]["value"] <= 7e-3


FOUR_CHIPS = """
import json, sys, types
sys.path[:0] = [{here!r}, {src!r}]
from chipbench import harness, spec
made = []
real = spec.system


def system(*a, **k):
    mod = real(*a, **k)
    make = mod.make_engine
    mod.make_engine = lambda *a, **k: made.append(make(*a, **k)) or made[-1]
    return mod


spec.system = system
args = types.SimpleNamespace(workload="tiny.four", seed=2**33 + 1,
                             seconds=1.0, trace=0, streams=None, control=0)
res = harness.run(args, {root!r}, {here!r}, device_check=lambda n: dict(
    platform="cpu", kind="TPU v5 lite", count=n))
st = made[0].state
print(json.dumps({{"correct": res["correct"], "check": res["check"],
                  "devices": len(st.ema.sharding.device_set),
                  "rows": sorted({{s.data.shape[0]
                                  for s in st.ema.addressable_shards}})}}))
"""


def test_four_chip_cell_shards_the_slots(tiny_root):
    """A cell on four chips serves one engine whose slot axis is sharded
    over a four-device "data" mesh (four CPU devices here), and is
    correct."""
    here = os.path.join(tiny_root, "benchmarks", "chip")
    bench = spec.benchmark(tiny_root)
    bench["workloads"].append({"name": "tiny.four", "config": "tiny",
                               "traffic": "tiny_open", "chips": 4,
                               "why": "slots sharded over four chips"})
    _dump(os.path.join(tiny_root, "BENCHMARK.json"), bench)
    _dump(os.path.join(here, "checks", "tiny.four.json"),
          spec.limits("tiny.open", here))
    code = FOUR_CHIPS.format(here=here, src=os.path.join(ROOT, "src"),
                             root=tiny_root)
    try:
        p = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu",
                              "XLA_FLAGS": "--xla_force_host_platform_"
                                           "device_count=4"})
    finally:
        bench["workloads"].pop()
        _dump(os.path.join(tiny_root, "BENCHMARK.json"), bench)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out["check"]
    assert out["devices"] == 4 and out["rows"] == [1]


def test_slots_that_do_not_divide_are_refused():
    from chipbench import model

    with pytest.raises(SystemExit):
        model.slot_mesh(4, 6)
    assert model.slot_mesh(1, 6) is None


@pytest.mark.parametrize("where,key,value", [
    ("config", "mlp", "swiglu"),
    ("config", "rope_theta", 10000.0),
    ("serving", "governor", {"budget_mw": 40.0}),
    ("serving", "temporal", False),
    ("serving", "rollout_ticks", 16),
    ("mix", "burst_share", 0.5),
    ("mix", "loop", "diurnal"),
    ("checks", "gaze_gap_max", None),
])
def test_unimplemented_keys_are_refused(tiny_root, where, key, value):
    """A config or mix that asks for what the harness does not implement
    (another serving mode, a governor, an unknown key), or a checks file
    without a limit for one of the system's compared numbers, stops the
    run before the device is touched, instead of running something
    else."""
    here = os.path.join(tiny_root, "benchmarks", "chip")
    conf = spec.config("tiny", here)
    mix = spec.traffic("tiny_open", here)
    if where == "config":
        conf[key] = value
    elif where == "serving":
        conf["serving"] = {**conf["serving"], key: value}
    else:
        mix[key] = value
    _dump(os.path.join(here, "configs", "refused.json"), conf)
    _dump(os.path.join(here, "traffic", "refused.json"), mix)
    bench = spec.benchmark(tiny_root)
    bench["workloads"].append({"name": "tiny.refused", "config": "refused",
                               "traffic": "refused", "chips": 1,
                               "why": "asks for what is not implemented"})
    _dump(os.path.join(tiny_root, "BENCHMARK.json"), bench)
    limits = spec.limits("tiny.open", here)
    if where == "checks":
        del limits[key]
    _dump(os.path.join(here, "checks", "tiny.refused.json"), limits)
    touched = []
    args = types.SimpleNamespace(workload="tiny.refused", seed=3, seconds=1.0,
                                 trace=0, streams=None, control=0)
    try:
        with pytest.raises(SystemExit):
            harness.run(args, tiny_root, here,
                        device_check=lambda n: touched.append(n))
    finally:
        bench["workloads"].pop()
        _dump(os.path.join(tiny_root, "BENCHMARK.json"), bench)
    assert touched == []


def test_open_traffic_gives_every_seed_the_same_work():
    """Seeds place cameras, phases, scenes and intruder times; the cameras
    per rate and kind, the intruders per activity rank, the churn bursts
    and swaps are the same for every seed, and activity is Zipf-skewed."""
    from chipbench import traffic

    mix = spec.traffic("surveil")
    seen = []
    for seed in (1, 2**31 + 5, 2**40 + 3):
        sch = traffic.build(mix, seed, 42.0, 256, 256)
        init = [sch.streams[s] for s in sch.initial]
        kinds = sorted((st.rate, st.static) for st in init)
        events = sorted((len(st.events) for st in init if st.static),
                        reverse=True)
        swaps = [(len(o), len(a)) for _, o, a in sch.churn]
        seen.append((kinds, events, swaps))
        # a camera churned in keeps its place's rate, kind and intruders
        for t, out, add in sch.churn:
            for o, a in zip(out, add):
                old, new = sch.streams[o], sch.streams[a]
                assert (old.rate, old.static) == (new.rate, new.static)
                assert all(e[0] >= t for e in new.events)
    assert seen[0] == seen[1] == seen[2]
    events = seen[0][1]
    assert sum(events) == round(mix["intruder_rate_per_s"] * len(events) * 42)
    assert events[0] > 10 * max(events[-1], 1)        # Zipf: a busy head


class SimClock:
    """The host clock of a simulated run: each reading takes 10 us, and
    a sleep passes as much time as it asks for."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-5
        return self.t - 1e-5

    def sleep(self, s):
        self.t += s


class SimEngine:
    """The engine's slots and churn as the serving loop sees them, on a
    simulated clock: a step takes one tick of ``tick_s`` (5% jitter),
    and ``freeze_s`` more where the tick spans a churn burst."""

    def __init__(self, clock, capacity, tick_s, freeze_s, bursts, seed):
        self.clock, self.capacity = clock, capacity
        self.tick_s, self.freeze_s, self.bursts = tick_s, freeze_s, bursts
        self.rng = np.random.default_rng(seed)
        self.slots = {}
        self.pending = []        # churn ops not yet flushed
        self.flushes = []        # the churn ops each step flushed
        self.served = []         # (sid, frame number) per frame served

    def admit(self, sid):
        if sid in self.slots or len(self.slots) == self.capacity:
            raise RuntimeError(f"admit {sid} to {sorted(self.slots)}")
        self.slots[sid] = min(set(range(self.capacity))
                              - set(self.slots.values()))
        self.pending.append(("admit", sid))

    def evict(self, sid):
        del self.slots[sid]
        self.pending.append(("evict", sid))

    def slot_of(self, sid):
        return self.slots[sid]

    def step(self, frames, block=False):
        assert set(frames) <= set(self.slots), "a frame of an evicted camera"
        self.flushes.append(self.pending)
        self.pending = []
        self.served.extend(frames.values())
        t0 = self.clock.t
        dt = self.tick_s * self.rng.uniform(0.95, 1.05)
        if any(t0 < t <= t0 + dt for t in self.bursts):
            dt += self.freeze_s
        self.clock.t += dt
        return types.SimpleNamespace(
            result=lambda: {s: np.zeros(4, np.float32) for s in frames})


def _serve_simulated(monkeypatch, sched, capacity, tick_s, freeze_s, seed,
                     preroll, seconds, drain_s=60.0):
    """``serving.serve_open`` over ``sched`` with a ``SimEngine``; a
    frame is served as its (camera, frame number)."""
    from chipbench import serving

    clock = SimClock()
    eng = SimEngine(clock, capacity, tick_s, freeze_s,
                    [t for t, _, _ in sched.churn], seed)
    for sid in sched.initial:
        eng.admit(sid)
    eng.pending = []
    monkeypatch.setattr(serving, "clock", clock)
    monkeypatch.setattr(serving, "time", types.SimpleNamespace(
        sleep=clock.sleep))
    monkeypatch.setattr(serving, "make_frame",
                        lambda sched, pool, sid, n: (sid, n))
    monkeypatch.setattr(serving, "fetch_served", lambda eng: (
        np.zeros((capacity, 1), np.int32), np.zeros(capacity),
        np.zeros(capacity)))
    rec = serving.Record(set())
    t0 = serving.serve_open(eng, sched, None, rec, seconds,
                            serving.Spans(False), drain_s, preroll)
    assert t0 == pytest.approx(preroll)
    return eng, rec


def _assert_served_once(sched, eng, rec, preroll, end):
    """Every frame the schedule makes due in the window is served once,
    none failed, and each churn burst reaches the engine in one flush."""
    due = sorted((sid, n) for sid, st in sched.streams.items()
                 for n in range(int((end - st.t_admit) * st.rate) + 2)
                 if preroll <= st.due(n) < min(st.t_evict, end))
    served = sorted(f for f in eng.served
                    if preroll <= sched.streams[f[0]].due(f[1]) < end)
    assert served == due
    assert rec.dropped == 0
    assert len(rec.due) + rec.dropped == len(due) == len(rec.due)
    flush_of = {op: i for i, ops in enumerate(eng.flushes + [eng.pending])
                for op in ops}
    for t, out, add in sched.churn:
        ops = [("evict", s) for s in out] + [("admit", s) for s in add]
        assert len({flush_of[op] for op in ops}) == 1, t


@pytest.mark.parametrize("seed", [3300000011, 2**31 + 99, 2**40 + 5])
@pytest.mark.parametrize("tick_ms,freeze_ms", [(5.0, 0.0), (8.6, 0.0),
                                               (12.0, 0.0), (8.6, 120.0)])
def test_open_loop_serves_every_frame_before_churn(monkeypatch, seed,
                                                   tick_ms, freeze_ms):
    """On the real surveil schedule, served on a simulated clock, every
    frame the schedule makes due in the window is served exactly once,
    also those of cameras that a churn burst evicts while their frames
    wait for a tick (the longest waits after a freeze across a burst),
    and each burst reaches the engine whole, in one churn flush."""
    from chipbench import traffic

    mix = spec.traffic("surveil")
    preroll, seconds = mix["preroll_s"], 40.0
    sched = traffic.build(mix, seed, preroll + seconds, 256, 256)
    eng, rec = _serve_simulated(monkeypatch, sched, mix["streams"],
                                tick_ms / 1e3, freeze_ms / 1e3, seed,
                                preroll, seconds, mix["drain_s"])
    _assert_served_once(sched, eng, rec, preroll, preroll + seconds)
    if freeze_ms:
        assert any(w is not None for _, _, w in rec.churn)


def test_open_loop_camera_evicted_before_its_first_frame(monkeypatch):
    """A camera churned in and out again before its first frame is due
    sends nothing: nothing of it is served or counted, and the second
    burst does not wait for it."""
    from chipbench import traffic

    st = traffic.Stream
    sched = traffic.Schedule(
        "open", {0: st(0, 10.0, False, 0, 0.0, 0.5, 0.01, []),
                 1: st(1, 10.0, False, 1, 0.0, np.inf, 0.02, []),
                 2: st(2, 10.0, False, 2, 0.5, 0.55, 0.09, []),
                 3: st(3, 10.0, False, 3, 0.55, np.inf, 0.03, [])},
        [0, 1], [(0.5, [0], [2]), (0.55, [2], [3])], 4, {})
    eng, rec = _serve_simulated(monkeypatch, sched, 2, 5e-3, 0.0, 1,
                                0.2, 1.0)
    _assert_served_once(sched, eng, rec, 0.2, 1.2)
    assert all(sid != 2 for sid, _ in eng.served)
    assert [w for _, _, w in rec.churn] == [None, None]


def test_exits_without_a_tpu(tmp_path):
    """Without a TPU the run exits non-zero and prints no result, also
    from a directory holding only BENCHMARK.json and the benchmark."""
    bare = tmp_path / "bare"
    shutil.copytree(HERE, bare / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), bare)
    for cwd in (ROOT, str(bare)):
        p = subprocess.run(
            [sys.executable, "benchmarks/chip/run.py", "--workload",
             "vit256.surveil", "--seed", "1", "--seconds", "1"],
            cwd=cwd, capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert p.returncode != 0
        assert '"correct"' not in p.stdout
