"""Metric arithmetic shared by the readers in ``metrics/``. Every reader
returns None where its cell gives it nothing to read; a share of a peak
or a roofline is never reported as 0 in place of a reading."""

from __future__ import annotations

import numpy as np

from . import costs, trace


def latencies_ms(ctx) -> np.ndarray:
    return (np.asarray(ctx.rec.done) - np.asarray(ctx.rec.due)) * 1e3


def frame_pct_ms(ctx, q: float):
    if ctx.loop != "open" or not ctx.rec.done:
        return None
    return float(np.percentile(latencies_ms(ctx), q))


def untraced_ticks(ctx) -> list:
    """Ticks that started before the profiler did (all of them without a
    trace): the ones whose host times are not stretched by it."""
    return [t for t in ctx.rec.ticks if t[0] < ctx.t_cut]


def frames_per_s(ctx):
    """Frames completed per second of window, over the untraced ticks
    (the whole window without a trace)."""
    done = [d for d in ctx.rec.done if d < ctx.t_cut]
    if not done:
        return None
    return len(done) / (max(done) - ctx.t0)


def host_dispatch_ms(ctx):
    """Mean host time inside ``step(block=False)`` per tick: staging,
    churn flush, fed-row scatter and dispatch."""
    t = untraced_ticks(ctx)
    if not t:
        return None
    return float(np.mean([d - c for _, c, d, _, _ in t]) * 1e3)


# the programs one tick runs on the device: the fed-row scatter and the
# step (the churn flush runs on a few ticks only and is left out)
TICK_PROGRAMS = ("jit_scatter", "jit_counted")


def device_s_per_tick(ctx):
    """Device seconds of one tick: the mean traced execution of each
    program a tick runs, summed."""
    if ctx.trace is None or not ctx.trace["ops"]:
        return None
    parts = [trace.module_s(ctx.trace, p) for p in TICK_PROGRAMS]
    return None if parts[-1] is None else sum(p or 0.0 for p in parts)


def idle_share(ctx):
    """Share of a tick's wall time in which the device runs nothing:
    device time per tick from the trace, tick wall time (step call to
    results) from the untraced ticks."""
    busy = device_s_per_tick(ctx)
    t = untraced_ticks(ctx)
    if busy is None or not t:
        return None
    wall = float(np.mean([done - c for _, c, _, done, _ in t]))
    return max(0.0, 1.0 - busy / wall)


def _kernel_share(ctx, kernel: str, per_tick_s) -> float | None:
    """Least time over measured time of ``kernel`` in the traced window,
    in %: the mean over ticks of ``per_tick_s(tick)`` (the least time of
    one call) times the calls the trace saw."""
    if ctx.trace is None or ctx.loop != "closed":
        return None
    secs, calls = trace.kernel_s(ctx.trace, ctx.kernels, kernel)
    if calls == 0 or secs <= 0:
        return None
    least = np.mean([per_tick_s(i) for i in range(len(ctx.rec.n_stale))])
    return 100.0 * calls * least / secs


def _pk(ctx):
    return costs.peaks(ctx.device["kind"])


def ragged_proj_roofline(ctx):
    c, k = ctx.conf, ctx.sizes["k"]
    pk = _pk(ctx)

    def least(i):
        rows = int(np.sum(ctx.rec.n_stale[i]))
        cost = costs.projection_min(rows, c["patch"] ** 2, c["n_vectors"],
                                    ctx.streams * k)
        return costs.roofline_s(cost["flops"], cost["bytes"],
                                pk["bf16_flops"], pk)

    return _kernel_share(ctx, "ip2_ragged_pallas", least)


def w8a8_embed_roofline(ctx):
    c, k = ctx.conf, ctx.sizes["k"]
    pk = _pk(ctx)
    cost = costs.w8a8_min(ctx.streams * k, c["n_vectors"], c["d_model"])
    t = costs.roofline_s(cost["ops"], cost["bytes"], pk["int8_ops"], pk)
    return _kernel_share(ctx, "quant_matmul_pallas", lambda i: t)


def delta_attn_roofline(ctx):
    c, k = ctx.conf, ctx.sizes["k"]
    pk = _pk(ctx)
    head = ctx.system.head_macs(c)

    def least(i):
        # a slot whose frame changed re-attends every query (exact reuse)
        q = int(np.sum(ctx.rec.macs[i] > head)) * k
        cost = costs.delta_attention_min(q, k, c["d_model"], c["n_heads"])
        return costs.roofline_s(cost["flops"], cost["bytes"],
                                pk["bf16_flops"], pk)

    return _kernel_share(ctx, "delta_attention_pallas", least)


def step_mfu(ctx):
    """Model operations of the frames served per second over the chip's
    peak for each part's precision, in %."""
    fps = frames_per_s(ctx)
    if fps is None or ctx.trace is None:
        return None
    pk = _pk(ctx)
    ops = ctx.system.frame_ops(ctx.conf, ctx.sizes)
    return 100.0 * fps * (ops["float"] / pk["bf16_flops"]
                          + ops["int8"] / pk["int8_ops"])
