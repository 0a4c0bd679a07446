"""Readers of the engine's own telemetry (``repro.serve.telemetry``): its
host-path spans and counters over the untraced window, the device time of
its layer scopes in the traced window, and its spans on the trace's clock.

The program is asked for its telemetry, through the system under test
(``ctx.system.telemetry()``), only when a reader runs, after the window.
A program without telemetry (an older commit) gives every reader here
nothing to read: they return None and do not raise.
"""

from __future__ import annotations

import bisect
import heapq

import numpy as np

from . import trace

STEP_PROGRAM = "jit_counted"
# the innermost engine spans: an idle instant one of them covers is
# theirs, before it is ``engine.step``'s or a harness phase's
ENGINE_CHILDREN = ("engine.stage", "engine.churn_flush", "engine.upload",
                   "engine.dispatch", "engine.result")
MAP_SLACK_S = 50e-6


def snapshot(ctx):
    """The program's telemetry snapshot, or None where it has none."""
    return ctx.system.telemetry()


def window_rows(ctx, snap=None):
    """Span rows (columns as in the snapshot, times in seconds of
    ``time.perf_counter``) that start in the untraced window,
    ``ctx.t0 <= start < ctx.t_cut``; None without telemetry, or where the
    ring has overwritten rows that may have started in the window."""
    snap = snapshot(ctx) if snap is None else snap
    if snap is None:
        return None
    sp = snap["spans"]
    start, end = sp["start_ns"] * 1e-9, sp["end_ns"] * 1e-9
    # rows are kept in the order they ended: every row overwritten ended
    # before the oldest kept one did
    if snap["dropped"] and (not len(end) or end[0] >= ctx.t0):
        return None
    keep = (start >= ctx.t0) & (start < ctx.t_cut)
    return {"name": sp["name"][keep], "start": start[keep], "end": end[keep],
            "tick": sp["tick"][keep], "count": sp["count"][keep]}


def mean_span_ms(ctx, name: str):
    """Mean duration in ms of the ``name`` spans that start in the
    untraced window (one a tick for every span but the churn flush, which
    is one a flush); None where there are none."""
    rows = window_rows(ctx)
    if rows is None:
        return None
    sel = rows["name"] == name
    if not sel.any():
        return None
    return float(np.mean(rows["end"][sel] - rows["start"][sel]) * 1e3)


def h2d_bytes_per_frame(ctx):
    """Bytes uploaded over frames staged, both counted by the program,
    over the untraced window."""
    rows = window_rows(ctx)
    if rows is None:
        return None
    frames = rows["count"][rows["name"] == "engine.stage"].sum()
    if frames == 0:
        return None
    return float(rows["count"][rows["name"] == "engine.upload"].sum()
                 / frames)


def self_times(ops) -> np.ndarray:
    """Seconds of each op in ``ops`` ((start, end, ...) tuples) in which
    no op that started later runs: an enclosing op such as ``cond`` or
    ``while`` keeps only what its nested ops leave uncovered, and each
    instant is counted once."""
    if not len(ops):
        return np.zeros(0)
    ends = [e for _, e, *_ in ops]
    bounds = sorted({x for s, e, *_ in ops for x in (s, e)})
    order = sorted(range(len(ops)), key=lambda i: ops[i][0])
    out = np.zeros(len(ops))
    live = []              # (-start, end, op index): the innermost first
    k = 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(order) and ops[order[k]][0] <= a:
            i = order[k]
            heapq.heappush(live, (-ops[i][0], ends[i], i))
            k += 1
        while live and ends[live[0][2]] <= a:
            heapq.heappop(live)
        # the innermost op is the one that started last (the shorter of
        # two that started together); an ended op below the top is
        # dropped once it reaches the top
        if live:
            out[live[0][2]] += b - a
    return out


def scope_device_s(ctx) -> dict | None:
    """Device seconds per layer scope (``unscoped`` for ops the scope map
    of the step program does not name a layer for) per execution of the
    step program wholly inside the traced window, averaged over the
    devices; None without device ops, a window or a scope map."""
    if ctx.trace is None or not any(ctx.trace["ops"]):
        return None
    snap = snapshot(ctx)
    if snap is None or STEP_PROGRAM not in snap["scopes"]:
        return None
    scopes = snap["scopes"][STEP_PROGRAM]
    lo, hi = trace.window(ctx.trace)
    per_device = []
    for ops, mods in zip(ctx.trace["ops"], ctx.trace.get("modules", [])):
        runs = [(s, e) for s, e, n in mods
                if n == STEP_PROGRAM and s >= lo and e <= hi]
        if not runs:
            continue
        # op names repeat across programs: keep the step's executions'
        inside = [op for op in ops
                  if any(s <= op[0] and op[1] <= e for s, e in runs)]
        sums: dict[str, float] = {}
        for op, t in zip(inside, self_times(inside)):
            layer = scopes.get(op[2], "unscoped")
            sums[layer] = sums.get(layer, 0.0) + t
        per_device.append({k: v / len(runs) for k, v in sums.items()})
    if not per_device:
        return None
    layers = {k for d in per_device for k in d}
    return {k: sum(d.get(k, 0.0) for d in per_device) / len(per_device)
            for k in layers}


def scope_device_ms(ctx, layers):
    """Device ms of ``layers`` together per step execution."""
    per = scope_device_s(ctx)
    if per is None:
        return None
    return 1e3 * sum(per.get(layer, 0.0) for layer in layers)


def to_trace_clock(ctx, snap=None) -> dict | None:
    """Per tick id, the offset that puts the engine's spans of that tick
    on the trace's clock (trace s = perf_counter s + offset), for every
    tick the trace holds; None where the spans cannot be placed.

    Each traced tick is one harness ``stage_dispatch`` span in the trace,
    opened just after the tick's ``step`` call time in ``ctx.rec.ticks``,
    and encloses that tick's ``engine.step``. The traced ticks are a run of
    consecutive ticks: the run is the one whose call times keep the most
    constant distance to the spans' starts. Refuses where any mapped
    ``engine.step`` lies outside its ``stage_dispatch`` by more than
    50 us, or a traced tick has no ``engine.step``."""
    snap = snapshot(ctx) if snap is None else snap
    if snap is None or ctx.trace is None:
        return None
    sd = np.asarray([(s, e) for n, s, e in ctx.trace["spans"]
                     if n == "stage_dispatch"]).reshape(-1, 2)
    calls = np.asarray([t[1] for t in ctx.rec.ticks])
    disps = np.asarray([t[2] for t in ctx.rec.ticks])
    m = len(sd)
    if m == 0 or len(calls) < m:
        return None
    spread = [np.ptp(sd[:, 0] - calls[i:i + m])
              for i in range(len(calls) - m + 1)]
    i0 = int(np.argmin(spread))
    sp = snap["spans"]
    is_step = sp["name"] == "engine.step"
    st_s, st_e = sp["start_ns"][is_step] * 1e-9, sp["end_ns"][is_step] * 1e-9
    st_tick = sp["tick"][is_step]
    order = np.argsort(st_s)
    st_s, st_e, st_tick = st_s[order], st_e[order], st_tick[order]
    out = {}
    for j in range(m):
        c, d = calls[i0 + j], disps[i0 + j]
        k = int(np.searchsorted(st_s, c))
        if k >= len(st_s) or st_s[k] > d:
            return None
        off = sd[j, 0] - c
        if (st_s[k] + off < sd[j, 0] - MAP_SLACK_S
                or st_e[k] + off > sd[j, 1] + MAP_SLACK_S):
            return None
        out[int(st_tick[k])] = float(off)
    return out


def idle_attribution(ctx, snap=None) -> dict | None:
    """Device idle seconds of the traced window by what the host was
    doing: the innermost engine span (on the trace's clock through
    :func:`to_trace_clock`), else the harness phase, else ``other``; and
    beside it each engine span's mean ms in traced and in untraced
    ticks. None where the spans cannot be placed on the trace's clock."""
    snap = snapshot(ctx) if snap is None else snap
    offsets = to_trace_clock(ctx, snap)
    if offsets is None or not ctx.trace["ops"]:
        return None
    lo, hi = trace.window(ctx.trace)
    sp = snap["spans"]
    labelled = []       # (priority, start, end, label): lowest wins
    traced: dict[str, list] = {}
    for name, s, e, t in zip(sp["name"], sp["start_ns"] * 1e-9,
                             sp["end_ns"] * 1e-9, sp["tick"]):
        off = offsets.get(int(t))
        if off is None:
            continue
        traced.setdefault(str(name), []).append(e - s)
        prio = 0 if name in ENGINE_CHILDREN else 1
        labelled.append((prio, s + off, e + off, str(name)))
    labelled += [(2, s, e, n) for n, s, e in ctx.trace["spans"]
                 if n in trace.HOST_PHASES]
    busy = trace.union(ctx.trace["ops"][0], lo, hi)
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    cuts = sorted({x for a, b in idle for x in (a, b)}
                  | {x for _, s, e, _ in labelled for x in (s, e)
                     if lo < x < hi})
    sums: dict[str, float] = {}
    idle_lo = [a for a, _ in idle]
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        k = bisect.bisect_right(idle_lo, mid) - 1
        if k < 0 or mid >= idle[k][1]:
            continue
        cover = [(p, lab) for p, s, e, lab in labelled if s <= mid < e]
        lab = min(cover)[1] if cover else "other"
        sums[lab] = sums.get(lab, 0.0) + (b - a)
    untraced = window_rows(ctx, snap)
    return {
        "idle_s": dict(sorted(sums.items(), key=lambda kv: -kv[1])),
        "window_s": hi - lo,
        "traced_ms": {k: 1e3 * float(np.mean(v)) for k, v in traced.items()},
        "untraced_ms": ({} if untraced is None else {
            str(n): 1e3 * float(np.mean((untraced["end"] - untraced["start"])
                                        [untraced["name"] == n]))
            for n in set(untraced["name"].tolist())}),
        "traced_ticks": len(offsets),
    }
