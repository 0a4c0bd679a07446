"""Smoke run of the saccade serving engine on TPU.

    python chip_smoke.py               # one chip: staged and fused phases
    python chip_smoke.py --four-chips  # sharded engine + fleet, four chips

Drives ``SaccadeEngine`` at the full ``ip2-vit`` widths
(``src/repro/configs/ip2_vit.py``: 256x256 frames, 32x32 patches, 192
vectors, k=16 of 64 patches, 6 layers, d_model 256) on seeded
``SceneStream`` frames with random weights, through the Pallas kernels of
the code-wire path, and compares every served logit with the plain jnp
reference engine (no kernels, a float embed) on the same frames.

One chip (default):

* ``staged`` — ``project_fn=ops.ip2_codes_fn`` (ragged projection with the
  fused edge ADC), ``quant_embed`` (w8a8 kernel), the temporal gate and
  the delta-gated backend with the ragged attention kernel. Six ticks,
  two of them partial-fed, one admit/evict churn, then a T=4 rollout.
* ``fused`` — the frontend megakernel (``fused_embed``), compared with the
  reference and, for the bitwise claim, with the staged kernel path.

Each phase prints its compile seconds, a warm tick time ending in
``block_until_ready`` and the largest logit gap to the reference; the
staged phase also prints how many frontend codes the kernel emits
bitwise equal to the jnp frontend. The reference embeds with the int8
weight grid the w8a8 kernel is programmed with (dequantized to float), so
both engines compute one function; a second reference with the float
embed weights measures what the int8 weights cost. The comparison runs at
float32 matmul precision, the served (timed) engine at the default; both
of those gaps are held to the limits set below from their readings. On
the bootstrap tick every slot is fresh and both engines serve the same
patch-energy gaze; later gazes come from each backend's own attention
and may part. Logits must stay within two ADC LSBs of the reference (the
quant-embed bound of tests/test_wire_format.py) on every stream-tick
served from the same gaze.

``--four-chips`` runs only the engine with its slot axis sharded over a
four-chip ``"data"`` mesh and a two-host ``SaccadeFleet``, each compared
with one unsharded single-chip engine on the same frames.

The last line is ``{"ok": true, "device": {...}}``. Without a TPU the
script exits non-zero before serving anything; there is no CPU fallback.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_STREAMS = 8
N_SCENES = 16
STATIC = 4          # streams 0..3 replay one scene: the temporal gate holds
# Limits of the two ungated-by-construction gaps, each 1.5x or more over
# its largest reading (TPU v5 lite and CPU, seed 0): the float-embed
# reference (what the int8 embed weights cost; 0.0198 chip, 0.0201 CPU)
# and the served engine at the default matmul precision against the
# float32 reference (0.0223 chip).
FLOAT_EMBED_LIMIT = 0.03
DEFAULT_PRECISION_LIMIT = 0.035


def _fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def _codes_fn(cfg):
    from repro.kernels import ops

    fc = cfg.frontend
    return ops.ip2_codes_fn(fc.patch, fc.adc, interpret=False)


def _configs(model, fused: bool):
    """(kernel-path cfg, reference cfg, kernel-engine kwargs, reference
    kwargs) at ``model``'s widths."""
    import dataclasses

    from repro.core.temporal import TemporalSpec
    from repro.models.vit import vit_config_from

    if fused:
        # the megakernel threads no temporal or backend cache
        ref = vit_config_from(model, saliency_layers="last")
        ker = dataclasses.replace(ref, quant_embed=True, fused_embed=True)
        return ker, ref, {}, {}
    ref = vit_config_from(
        model, frontend_kw=dict(temporal=TemporalSpec(delta_threshold=1e-4)),
        saliency_layers="last")
    ker = dataclasses.replace(ref, quant_embed=True, delta_kernel=True)
    serve = dict(temporal=True, backend_delta=True)
    return ker, ref, dict(project_fn=_codes_fn(ker), **serve), serve


class Scenes:
    """Seeded frames: stream s at tick t. Streams below STATIC replay one
    scene (the temporal gate and the backend cache hold them); the others
    see a new scene every tick."""

    def __init__(self, seed: int, image: int):
        from repro.data.pipeline import SceneStream

        self.rgb, _ = SceneStream(seed=seed, image=image).batch(0, N_SCENES)

    def frame(self, sid: int, t: int) -> np.ndarray:
        return self.rgb[(sid if sid < STATIC else sid + t) % N_SCENES]

    def tick(self, sids, t: int) -> dict:
        return {s: self.frame(s, t) for s in sids}


def _gaze(eng, sids) -> dict:
    """Per stream, the gaze the next step serves: ``None`` for a fresh
    slot (it bootstraps in-step from patch energy), else its indices."""
    st = eng.state
    ages, idx = np.asarray(st.frame_age), np.asarray(st.indices)
    return {s: (None if ages[eng.slot_of(s)] == 0
                else idx[eng.slot_of(s)].copy()) for s in sids}


def _same_gaze(a, b) -> bool:
    if a is None or b is None:
        return a is None and b is None
    return bool(np.array_equal(a, b))


class Compare:
    """Logit gaps between an engine under test and its reference. A
    stream is compared only while both engines have served it the same
    gaze: once the gazes part, the streams see different patches and
    their caches hold different rows, so it is dropped for good."""

    def __init__(self):
        self.max_abs = 0.0
        self.bitwise = True
        self.n = self.n_same_gaze = self.argmax_agree = 0
        self.parted: set = set()

    def add(self, out_a: dict, out_b: dict, gaze_a: dict, gaze_b: dict):
        for sid in out_a:
            a, b = np.asarray(out_a[sid]), np.asarray(out_b[sid])
            if not np.all(np.isfinite(a)) or a.shape != b.shape:
                _fail(f"stream {sid}: logits {a} not finite or misshapen")
            self.n += 1
            self.argmax_agree += int(np.argmax(a) == np.argmax(b))
            if not _same_gaze(gaze_a[sid], gaze_b[sid]):
                self.parted.add(sid)
            if sid not in self.parted:
                self.n_same_gaze += 1
                self.max_abs = max(self.max_abs, float(np.abs(a - b).max()))
                self.bitwise &= bool(np.array_equal(a, b))

    def record(self) -> dict:
        return {"max_abs_dlogit": self.max_abs,
                "bitwise": self.bitwise,
                "stream_ticks": self.n,
                "stream_ticks_same_gaze": self.n_same_gaze,
                "argmax_agreement": self.argmax_agree / max(self.n, 1)}


def _serve(engines, schedule, scenes) -> list[list[tuple[dict, dict]]]:
    """Run ``schedule`` (list of (churn ops, fed sids)) on every engine.
    Returns, per engine, the (gaze served, logits) of every tick."""
    runs = [[] for _ in engines]
    for t, (churn, fed) in enumerate(schedule):
        for op, sid in churn:
            for e in engines:
                getattr(e, op)(sid)
        frames = scenes.tick(fed, t)
        for e, run in zip(engines, runs):
            gaze = _gaze(e, fed)
            run.append((gaze, e.step(frames)))
    return runs


def _compare(run_a, run_b) -> tuple[Compare, Compare]:
    """(all ticks, bootstrap tick alone) comparisons of two engines' runs."""
    every, boot = Compare(), Compare()
    for t, ((ga, oa), (gb, ob)) in enumerate(zip(run_a, run_b)):
        every.add(oa, ob, ga, gb)
        if t == 0:
            boot.add(oa, ob, ga, gb)
    return every, boot


def _warm_tick_ms(eng, frames, n: int = 5) -> list[float]:
    """Wall time of full ticks on a compiled engine, each ending in
    ``block_until_ready`` on the logits and the donated state."""
    import jax

    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        out = eng.step(frames, block=False)
        jax.block_until_ready((out.result(), eng.state))
        times.append((time.perf_counter() - t0) * 1e3)
    return times


def _compile(eng):
    """(seconds, compiled step) of the engine's one-tick program."""
    t0 = time.perf_counter()
    compiled = eng.compile_step()
    return time.perf_counter() - t0, compiled


def _code_agreement(params, cfg, scenes) -> dict:
    """Frontend wire codes of the ragged kernel vs the jnp projection on
    the first tick's frames, energy-selected: the bitwise claim the CPU
    interpret-mode tests make."""
    import jax
    import jax.numpy as jnp

    from repro.core.frontend import apply_frontend
    fc = cfg.frontend
    rgb = jnp.asarray(np.stack([scenes.frame(s, 0) for s in range(N_STREAMS)]))
    kern = _codes_fn(cfg)
    run = jax.jit(lambda p, x, fn: apply_frontend(
        p, x, fc, mode="compact", project_fn=fn).features,
        static_argnums=(2,))
    a = np.asarray(run(params["ip2"], rgb, kern)).astype(np.int32)
    b = np.asarray(run(params["ip2"], rgb, None)).astype(np.int32)
    return {"codes_equal_frac": float(np.mean(a == b)),
            "codes_max_abs_diff": int(np.abs(a - b).max())}


def phase_one_chip(model, fused: bool, seed: int, device: dict,
                   hbm_bytes: int) -> dict:
    """One served phase. The served engine runs at the default matmul
    precision and is timed. The correctness check runs the same kernel
    path and the reference at float32 matmul precision: at the default
    precision the TPU rounds f32 matmul operands to bf16, which the
    reference must not do."""
    import dataclasses

    import jax

    from repro.models.vit import init_vit, prepare_quant_embed
    from repro.serve.engine import SaccadeEngine

    name = "fused" if fused else "staged"
    ker_cfg, ref_cfg, ker_kw, ref_kw = _configs(model, fused)
    params = init_vit(jax.random.PRNGKey(seed), ref_cfg)
    qparams = prepare_quant_embed(params)
    bound = 2.0 * ker_cfg.frontend.adc.lsb
    scenes = Scenes(seed, ker_cfg.frontend.image_h)
    sids = list(range(N_STREAMS))

    def build(cfg, p, **kw):
        e = SaccadeEngine(cfg, p, capacity=N_STREAMS, **kw)
        for s in sids:
            e.admit(s)
        return e

    served = build(ker_cfg, qparams, **ker_kw)
    compile_s, compiled = _compile(served)
    n_kernels = compiled.as_text().count("tpu_custom_call")
    ma = compiled.memory_analysis()
    step_bytes = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    _emit({"phase": name, "device": device["kind"],
           "compile_s": compile_s, "tpu_custom_call": n_kernels,
           "step_bytes": step_bytes, "hbm_bytes": hbm_bytes})
    if n_kernels == 0:
        _fail(f"{name}: no tpu_custom_call in the compiled step")
    if step_bytes > hbm_bytes:
        _fail(f"{name}: step needs {step_bytes} B, the chip has {hbm_bytes}")

    late = N_STREAMS        # the stream admitted by the churn
    after = [s for s in sids if s != N_STREAMS - 1] + [late]
    churn = (("evict", N_STREAMS - 1), ("admit", late))
    if fused:
        schedule = [((), sids), ((), [0, 2, 4, 6, 7]), (churn, after)]
    else:
        schedule = [((), sids), ((), sids), ((), [0, 2, 4, 6, 7]),
                    (churn, after), ((), after), ((), [1, 3, 5, late])]

    # the reference embeds with the weights the w8a8 path programs (int8
    # grid, dequantized), so the two engines compute one function; the
    # float-embed engine measures what the int8 weights themselves cost
    w8, s_w = qparams["embed_q"]
    prog = {**params, "embed": w8.astype(np.float32) * s_w[None, :]}
    with jax.default_matmul_precision("float32"):
        engines = [build(ker_cfg, qparams, **ker_kw),
                   build(ref_cfg, prog, **ref_kw),
                   build(ref_cfg, params, **ref_kw)]
        if fused:
            # the staged kernel path the megakernel claims bitwise
            # equality to
            staged_cfg = dataclasses.replace(ker_cfg, fused_embed=False)
            engines.append(build(staged_cfg, qparams,
                                 project_fn=_codes_fn(staged_cfg)))
        runs = _serve(engines, schedule, scenes)
        if not fused:
            # T=4 rollout, one tick partial and one all-hold
            roll = [scenes.tick(after, 6), scenes.tick([0, 1, 2], 7),
                    scenes.tick(after, 8), {}]
            before = [_gaze(e, after) for e in engines[:2]]
            ro_k, ro_r = (e.step_rollout(roll) for e in engines[:2])
            end = [_gaze(e, after) for e in engines[:2]]
    every, boot = _compare(runs[0], runs[1])
    rec = {"phase": name, "device": device["kind"], "bound": bound,
           "precision": "float32", "bootstrap": boot.record(),
           **every.record(), "streams_parted": len(every.parted),
           "float_embed_reference": _compare(runs[0], runs[2])[0].record()}
    if fused:
        rec["fused_vs_staged"] = _compare(runs[0], runs[3])[0].record()
    else:
        # the gaze inside a rollout is not observable: a stream counts
        # when the engines agreed on it before and after the rollout
        rcmp = Compare()
        rcmp.parted = set(every.parted) | {
            s for s in after if not (_same_gaze(before[0][s], before[1][s])
                                     and _same_gaze(end[0][s], end[1][s]))}
        for tk, tr in zip(ro_k, ro_r):
            rcmp.add(tk, tr, before[0], before[0])
        rec["rollout"] = rcmp.record()
        rec["rollout_traces"] = [e.n_rollout_traces for e in engines[:2]]
        rec.update(_code_agreement(params, ker_cfg, scenes))

    # the served engine, default precision, against the float32 reference
    served_run = _serve([served], schedule, scenes)[0]
    rec["default_precision_vs_reference"] = _compare(
        served_run, runs[1])[0].record()
    rec["n_traces"] = [e.n_traces for e in [served, *engines]]
    ticks = _warm_tick_ms(served, scenes.tick(after, 9))
    rec["warm_tick_ms"] = ticks
    rec["warm_tick_ms_median"] = float(np.median(ticks))
    _emit(rec)

    if any(n != 1 for n in rec["n_traces"]):
        _fail(f"{name}: step retraced: n_traces={rec['n_traces']}")
    # every slot is fresh on the bootstrap tick, so both engines serve
    # the patch-energy gaze: each stream must have been compared
    if rec["bootstrap"]["stream_ticks_same_gaze"] != N_STREAMS:
        _fail(f"{name}: bootstrap gaze differs: {rec['bootstrap']}")
    worst = max(rec["max_abs_dlogit"],
                rec.get("rollout", {}).get("max_abs_dlogit", 0.0))
    if worst > bound:
        _fail(f"{name}: |dlogit| {worst} > {bound}")
    for key, limit in (("float_embed_reference", FLOAT_EMBED_LIMIT),
                       ("default_precision_vs_reference",
                        DEFAULT_PRECISION_LIMIT)):
        if rec[key]["max_abs_dlogit"] > limit:
            _fail(f"{name}: {key} |dlogit| "
                  f"{rec[key]['max_abs_dlogit']} > {limit}")
    return rec


def phase_four_chips(model, seed: int, device: dict) -> dict:
    """Engine sharded over a 4-chip "data" mesh and a 2-host fleet, both
    against one unsharded engine on device 0, same frames."""
    import jax

    from repro.launch.mesh import make_mesh
    from repro.models.vit import init_vit, prepare_quant_embed
    from repro.serve.engine import SaccadeEngine
    from repro.serve.fleet import SaccadeFleet, make_fleet_meshes

    capacity = 32
    ker_cfg, _, ker_kw, _ = _configs(model, fused=False)
    params = prepare_quant_embed(
        init_vit(jax.random.PRNGKey(seed), ker_cfg))
    bound = 2.0 * ker_cfg.frontend.adc.lsb
    scenes = Scenes(seed, ker_cfg.frontend.image_h)

    mesh = make_mesh((len(jax.devices()),), ("data",))
    sids = list(range(capacity))
    cmp_sh, cmp_fl = Compare(), Compare()
    # float32 matmuls, as in the one-chip check: the engines differ only
    # in placement, so their gap is the sharding's alone
    with jax.default_matmul_precision("float32"):
        sharded = SaccadeEngine(ker_cfg, params, capacity=capacity,
                                mesh=mesh, **ker_kw)
        single = SaccadeEngine(ker_cfg, params, capacity=capacity, **ker_kw)
        fleet = SaccadeFleet(ker_cfg, params, n_hosts=2,
                             capacity=capacity // 2,
                             meshes=make_fleet_meshes(2), **ker_kw)
        for s in sids:
            sharded.admit(s)
            single.admit(s)
            fleet.submit(s)
        fleet.drain()

        for t, fed in enumerate([sids, sids[::2], sids]):
            frames = scenes.tick(fed, t)
            g_single, g_sh = _gaze(single, fed), _gaze(sharded, fed)
            g_fl = {}
            for e in fleet.engines:
                g_fl.update(_gaze(e, [s for s in fed if s in e.stream_ids]))
            o_single = single.step(frames)
            cmp_sh.add(sharded.step(frames), o_single, g_sh, g_single)
            cmp_fl.add(fleet.step(frames), o_single, g_fl, g_single)

    st = sharded.state
    spread = {
        "state_devices": len(st.ema.sharding.device_set),
        "cache_devices": len(st.cache.features.sharding.device_set),
        "bcache_devices": len(jax.tree.leaves(st.bcache)[0]
                              .sharding.device_set),
        "shard_rows": sorted({s.data.shape[0]
                              for s in st.ema.addressable_shards}),
        "fleet_devices": [len(e.state.ema.sharding.device_set)
                          for e in fleet.engines],
    }
    rec = {"phase": "four_chips", "device": device["kind"],
           "n_devices": len(jax.devices()), "bound": bound,
           "precision": "float32",
           "sharded_vs_single": cmp_sh.record(),
           "fleet_vs_single": cmp_fl.record(),
           "n_traces": {"sharded": sharded.n_traces,
                        "single": single.n_traces,
                        "fleet": fleet.n_traces},
           **spread}
    _emit(rec)
    n = len(jax.devices())
    if spread["state_devices"] != n or spread["cache_devices"] != n:
        _fail(f"sharded state spans {spread} devices, not {n}")
    if spread["shard_rows"] != [capacity // n]:
        _fail(f"uneven slot shards {spread['shard_rows']}")
    if spread["fleet_devices"] != [n // 2, n // 2]:
        _fail(f"fleet host meshes span {spread['fleet_devices']}")
    for key in ("sharded_vs_single", "fleet_vs_single"):
        if rec[key]["max_abs_dlogit"] > bound:
            _fail(f"{key}: |dlogit| {rec[key]['max_abs_dlogit']} > {bound}")
    if rec["n_traces"]["sharded"] != 1 or rec["n_traces"]["fleet"] != [1, 1]:
        _fail(f"retraced: {rec['n_traces']}")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-engine and fleet phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax sees {devices[0].platform}); "
              f"this script does not run on the CPU", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    from repro.configs.registry import get_config
    from repro.roofline.peaks import peaks_for

    enable_compile_cache()
    model = get_config("ip2-vit")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if args.four_chips:
        phase_four_chips(model, args.seed, device)
    else:
        hbm = peaks_for(device["kind"]).hbm_bytes
        phase_one_chip(model, False, args.seed, device, hbm)
        phase_one_chip(model, True, args.seed, device, hbm)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
