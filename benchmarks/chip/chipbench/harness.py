"""One run of one cell: load, warm up, measure, check, report.

Set-up (``setup_s``) runs from process start to the first timed tick:
the weights are made on the device from the seed, the scene pool on the
host from the seed, every program the cell's traffic uses is compiled or
loaded from JAX's persistent cache, and the window's first cameras are
admitted. The window then serves the traffic for ``--seconds``. With
``--trace 1`` the window's last 1.5 s are traced, and the run reports
the cell's per-layer metrics instead of its end-to-end ones. After the window: peak device memory is read, the engine is freed,
and the sampled cameras are replayed through the plain reference.
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import time

import numpy as np

from . import check, model, scenes, serving, spec, traffic, trace

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def require_chip(chips: int) -> dict:
    """The device record, or exit non-zero without a TPU or with fewer
    chips than the cell needs. There is no CPU fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: needs {chips} TPU chip(s); jax sees "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_cache(root: str) -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program in it."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Ctx:
    """What the metric readers read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class TraceWindow:
    """Profiles the part of the window from ``start`` to ``stop`` seconds,
    marked by a span the reduction finds. The profiler starts ``settle``
    seconds before the span opens, so its own start-up stall lies outside
    what is reduced."""

    def __init__(self, on: bool, out_dir: str, start: float, stop: float,
                 settle: float = 0.5):
        self.on, self.dir = on, out_dir
        self.start, self.stop, self.settle = start, stop, settle
        self.state = 0
        self.ann = None
        self.costs = {}          # seconds the loop spent in profiler calls

    def __call__(self, now: float) -> None:
        import jax

        if not self.on:
            return
        t = time.perf_counter()
        if self.state == 0 and now >= self.start - self.settle:
            # host spans only: the Python tracer would record every call
            # of the serving loop and slow it many times over
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.state = 1
            self.costs["start_trace_s"] = time.perf_counter() - t
        elif self.state == 1 and now >= self.start:
            self.ann = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
            self.ann.__enter__()
            self.state = 2
        elif self.state == 2 and now >= self.stop:
            self.close()

    def close(self) -> None:
        import jax

        t = time.perf_counter()
        if self.state == 2:
            self.ann.__exit__(None, None, None)
        if self.state in (1, 2):
            jax.profiler.stop_trace()
            self.state = 3
            self.costs["stop_trace_s"] = time.perf_counter() - t


def run(args, root: str, here: str = spec.HERE, t_start: float | None = None,
        device_check=require_chip) -> dict:
    t_start = time.perf_counter() if t_start is None else t_start
    marks = [("imports", time.perf_counter())]
    bench = spec.benchmark(root)
    cell = spec.cell(bench, args.workload)
    conf = spec.config(cell["config"], here)
    mix = spec.traffic(cell["traffic"], here)
    limits = spec.limits(cell["name"], here)
    mets = spec.metrics_for(bench, cell["name"], bool(args.trace))
    readers = {m["name"]: spec.reader(m["name"], here) for m in mets}
    ref = spec.reference(conf["reference"], here)
    system = spec.system(conf["reference"], here)
    # what the harness does not implement is refused before any run
    system.check_config(conf)
    check.check_limits(limits, system.NUMBERS)
    traffic.check_keys(mix)
    device = device_check(cell["chips"])
    marks.append(("devices", time.perf_counter()))
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        raise SystemExit(f"chipbench: the program is not in {root}/src")
    sys.path.insert(0, os.path.join(root, "src"))

    import jax

    enable_cache(root)
    compiles = {"on": False, "n": 0}

    def on_event(name, secs, **kw):
        if compiles["on"] and name == COMPILE_EVENT:
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)

    sz = ref.sizes(conf)
    seconds = float(args.seconds)
    n_streams = int(args.streams or mix["streams"])
    preroll = float(mix.get("preroll_s", 0.0))
    sched = traffic.build(mix, args.seed, preroll + seconds, conf["frame_h"],
                          conf["frame_w"], n_streams)
    pool = scenes.scene_pool(args.seed, mix["scenes"], conf["frame_h"],
                             conf["frame_w"])
    sample = check.sample(sched, args.seed, mix["check_streams"],
                          preroll + seconds)
    marks.append(("traffic_scenes", time.perf_counter()))
    params = system.make_weights(conf, model.seed_key(args.seed))
    jax.block_until_ready(params)
    marks.append(("weights", time.perf_counter()))
    t_weights = time.perf_counter() - t_start
    trace_dir = os.path.join(root, ".chipbench_trace",
                             f"{cell['name']}.{args.seed}")
    # the profiler stretches every tick it sees (a host cost per device
    # op), so it traces the window's last seconds: the ticks before it
    # give the host-clock numbers, the trace gives device time
    span = min(1.5, seconds / 4)
    tw = TraceWindow(bool(args.trace), trace_dir, seconds - span - 0.25,
                     seconds - 0.25)
    spans = serving.Spans(bool(args.trace))
    rec = serving.Record(sample)
    with jax.default_matmul_precision(conf["matmul_precision"]):
        eng = system.make_engine(
            conf, params, capacity=n_streams,
            mesh=model.slot_mesh(cell["chips"], n_streams))
        kernels = (trace.kernel_map(eng.compile_step().as_text())
                   if args.trace else {})
        fed = (range(1, n_streams + 1) if sched.loop == "open"
               else [n_streams])
        serving.warm_up(eng, sched, pool, fed)
        marks.append(("engine_warm_up", time.perf_counter()))
        for sid in sched.initial:
            eng.admit(sid)
        jax.block_until_ready(eng.state)
        # what set-up left lives for the whole run: out of the collector's
        # sight, so that its passes in the window, as in any server, scan
        # only what the window makes
        gc.collect()
        gc.freeze()
        # compiles are counted from the pre-roll on: it runs no new shape
        compiles["on"] = True
        if sched.loop == "open":
            t0 = serving.serve_open(eng, sched, pool, rec, seconds, spans,
                                    mix["drain_s"], preroll, on_tick=tw)
        else:
            t0 = serving.serve_closed(eng, sched, pool, rec, seconds, spans,
                                      on_tick=tw)
        setup_s = t0 - t_start
        marks.append(("admit_preroll", t0))
        compiles["on"] = False
        tw.close()
        n_traces = eng.n_traces
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in jax.local_devices())
    device["memory_peak_bytes"] = mem
    del eng
    gc.collect()

    tr = None
    breakdown = None
    if args.trace:
        tr = trace.load(trace_dir)
        lo, hi = trace.window(tr)
        device["busy_s"] = trace.busy_s(tr)
        device["window_s"] = hi - lo
        breakdown = {"device_ops": trace.device_ops(tr, kernels),
                     "idle_gaps": trace.idle_gaps(tr)}
        shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = Ctx(system=system, conf=conf, mix=mix, sizes=sz, rec=rec, t0=t0,
              seconds=seconds, setup_s=setup_s,
              window_compiles=compiles["n"], trace=tr, kernels=kernels,
              device=device, loop=sched.loop, streams=n_streams,
              t_cut=(t0 + tw.start - tw.settle) if args.trace else math.inf)
    metrics = {}
    for m in mets:
        v = readers[m["name"]](ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the comparison, after the window, on the sampled cameras
    t_ref = time.perf_counter()
    sids = sorted(sample)
    xs, served, mask = check.inputs(sched, rec, sids, sz["k"],
                                    mix["check_t_bucket"])
    w = system.reference_weights(params)
    ref_out, gaps = check.replay(ref, conf, w, pool, xs, "float32",
                                 mix["check_block"])
    nums = check.compare(system, served, ref_out, gaps, mask)
    correct, shown = check.verdict(nums, limits, system.NUMBERS)
    control = None
    if getattr(args, "control", 0):
        lo_out, lo_gaps = check.replay(ref, conf, w, pool, xs, "bf16x3",
                                       mix["check_block"])
        control = check.compare(system, lo_out, ref_out, lo_gaps, mask)
    ref_s = time.perf_counter() - t_ref

    attempted = len(rec.due) + rec.dropped
    lat = (np.asarray(rec.done) - np.asarray(rec.due)) * 1e3
    third = max(1, len(lat) // 3)
    order = np.argsort(rec.due)
    stale = np.concatenate(rec.n_stale) if rec.n_stale else np.zeros(0)
    macs = np.concatenate(rec.macs) if rec.macs else np.zeros(0)
    head = system.head_macs(conf)
    info = {
        "cell": cell["name"], "seed": args.seed, "trace": int(args.trace),
        "device": device["kind"], "streams": n_streams,
        "knee": mix.get("knee"),
        "ticks": len(rec.ticks), "frames": len(rec.done),
        "dropped": rec.dropped, "n_traces": n_traces,
        "churn_deferred": sum(w is not None for _, _, w in rec.churn),
        "churn_defer_ms_max": max([(a - w) * 1e3 for _, a, w in rec.churn
                                   if w is not None], default=0.0),
        "setup_weights_s": t_weights, "setup_s": setup_s,
        "setup_parts_s": {n: t - p for (n, t), (_, p) in
                          zip(marks, [("", t_start)] + marks[:-1])},
        "generator_ms_p50": float(np.median(rec.gen_s) * 1e3) if rec.gen_s else None,
        "generator_ms_p99": float(np.percentile(rec.gen_s, 99) * 1e3) if rec.gen_s else None,
        "fed_per_tick": float(np.mean([t[4] for t in rec.ticks])) if rec.ticks else None,
        "latency_ms_p99": float(np.percentile(lat, 99)) if len(lat) else None,
        "latency_ms_p50_first_third": float(np.median(lat[order[:third]])) if len(lat) else None,
        "latency_ms_p50_last_third": float(np.median(lat[order[-third:]])) if len(lat) else None,
        "frontend_recompute": float(np.mean(stale) / sz["k"]) if stale.size else None,
        "backend_cached_share": float(np.mean(macs == 0)) if macs.size else None,
        "backend_partial_share": float(np.mean((macs > 0) & (macs <= head))) if macs.size else None,
        **tick_stats(rec, t0), **tw.costs,
        "memory_peak_bytes": mem, "window_compiles": compiles["n"],
        "reference_s": ref_s, "compared": nums, "control": control,
    }
    return {"info": info, "correct": bool(correct), "attempted": attempted,
            "failed": rec.dropped, "metrics": metrics, "device": device,
            "breakdown": breakdown, "check": shown}


def tick_stats(rec, t0: float) -> dict:
    """Tick times in ms: whole tick (step call to results), dispatch,
    fetch; and the five slowest ticks as (start s, dispatch ms, fetch
    ms, fed)."""
    if not rec.ticks:
        return {}
    t = np.asarray([x[1:5] for x in rec.ticks])
    tick, disp, fetch = ((t[:, 2] - t[:, 0]) * 1e3, (t[:, 1] - t[:, 0]) * 1e3,
                         (t[:, 2] - t[:, 1]) * 1e3)
    slow = np.argsort(tick)[-5:][::-1]
    return {"tick_ms_p50": float(np.median(tick)),
            "tick_ms_p99": float(np.percentile(tick, 99)),
            "dispatch_ms_p99": float(np.percentile(disp, 99)),
            "fetch_ms_p99": float(np.percentile(fetch, 99)),
            "slow_ticks": [[float(t[i, 0] - t0), float(disp[i]),
                            float(fetch[i]), int(t[i, 3])] for i in slow]}


def report(res: dict) -> None:
    """The earlier line, the compared numbers on standard error, and the
    result as the last line of standard output."""
    print(json.dumps(_finite(res["info"]), default=_num), flush=True)
    for name, v in res["check"].items():
        rel = ">=" if name == "frames" else "<="
        print(f"check {name} {v['value']!r} (limit {rel} {v['limit']!r})",
              file=sys.stderr, flush=True)
    out = {k: res[k] for k in ("correct", "attempted", "failed", "metrics",
                               "device")}
    if res["breakdown"] is not None:
        out["breakdown"] = res["breakdown"]
    out["check"] = res["check"]
    print(json.dumps(_finite(out), default=_num), flush=True)


def _num(x):
    if isinstance(x, (np.floating, np.integer)):
        return _finite(x.item())
    raise TypeError(type(x))


def _finite(x):
    """JSON has no infinities or NaNs: such a number is written as a
    string."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    return x
