"""The serving loop: continuous batching of camera frames, as a user's
server would run it, with every time taken on the host clock.

Each tick, as soon as the previous tick's outputs are on the host and at
least one frame is due, the loop hands the engine every due frame, oldest
first and one per camera, with ``step(frames, block=False)``, then
fetches the outputs with ``handle.result()`` and the gaze each camera is
told to convert next (the served indices). A frame's latency runs from
the moment it was due to the moment its output is on the host. Churn
(evict, then admit) is applied between ticks at its scheduled time, or
once the evicted cameras' last frames are served, where they are still
queued then.
"""

from __future__ import annotations

import contextlib
import heapq
import math
import time

import jax
import numpy as np

from . import scenes as scenes_mod

clock = time.perf_counter


class Spans:
    """Host spans written into the profiler's trace when tracing, so the
    device's idle gaps can be named by what the host was doing."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if self.on:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


class Record:
    """What one run served: per frame (due, done), per tick (start,
    dispatched, done, fed), and the sampled cameras' outputs."""

    def __init__(self, sample: set):
        self.sample = sample
        self.due, self.done = [], []
        # per tick: (start, step called, step returned, results in, fed)
        self.ticks = []
        self.gen_s = []                  # host seconds spent making frames
        # sid -> [(n, gaze, output)]: the output as ``result()`` gives it,
        # a pytree of arrays (the logits alone are a one-leaf pytree)
        self.out = {s: [] for s in sample}
        # per tick, over the fed slots: recomputed patches, backend MACs
        self.n_stale, self.macs = [], []
        # frames due in the window and never served
        self.dropped = 0
        # per churn burst, in seconds of the schedule: (due, applied, when
        # it first waited for a tick to serve its cameras' frames, or None)
        self.churn = []


def fetch_served(eng):
    """One transfer of (served gaze, recomputed patches, backend MACs)
    per slot."""
    st = eng.state
    return jax.device_get((st.bcache.indices, st.cache.n_stale,
                           st.events_last.backend_macs))


def tick(eng, frames: dict, rec: Record, spans: Spans, t_start: float,
         meta: dict, timed: bool = True):
    """Dispatch one tick and wait for its results. ``meta`` maps each fed
    camera to (frame number, due time or None where not measured); an
    un-``timed`` tick (pre-roll) records only the sampled outputs."""
    t_call = clock()
    with spans("stage_dispatch"):
        handle = eng.step(frames, block=False)
    t_disp = clock()
    with spans("fetch"):
        out = handle.result()
        gaze, n_stale, macs = fetch_served(eng)
    t_done = clock()
    if timed:
        rec.ticks.append((t_start, t_call, t_disp, t_done, len(frames)))
        slots = [eng.slot_of(s) for s in frames]
        rec.n_stale.append(n_stale[slots])
        rec.macs.append(macs[slots])
    for sid, (n, due) in meta.items():
        if due is not None:
            rec.due.append(due)
            rec.done.append(t_done)
        if sid in rec.sample:
            rec.out[sid].append((n, gaze[eng.slot_of(sid)].copy(),
                                 jax.tree.map(np.array, out[sid])))


def make_frame(sched, pool, sid: int, n: int) -> np.ndarray:
    scene, box, colour = sched.frame_spec(sid, n)
    return scenes_mod.paint(pool[scene], box, colour)


def window_frames(st, lo: float, hi: float, n: int = 0) -> int:
    """How many of camera ``st``'s frames, from its ``n``-th on, the
    schedule makes due in ``[lo, min(t_evict, hi))``."""
    hi = min(st.t_evict, hi)
    count = 0
    while st.due(n) < hi:
        count += st.due(n) >= lo
        n += 1
    return count


def serve_open(eng, sched, pool, rec: Record, seconds: float,
               spans: Spans, drain_s: float, preroll: float = 0.0,
               on_tick=lambda now: None) -> float:
    """Open loop. The schedule's first ``preroll`` seconds are served
    before the window opens, so the window sees the steady state and not
    every camera's first frame at once; then frames due in the window's
    ``seconds`` are measured, and those still waiting when it closes are
    served after it (for up to ``drain_s``). ``on_tick(now)`` is called
    between ticks with the time since the window opened. Returns the
    window's start on the host clock.

    Every frame a camera sent before its eviction is served: a churn
    burst at ``t`` applies, whole (its evictions, then its admits), at
    the first pass between ticks where no frame of a camera it evicts is
    still queued (all of them are due before ``t``). What is attempted is
    what the schedule makes due in the window; what failed is the part of
    it still unserved when the drain ends."""
    end = preroll + seconds
    heap = []   # (due, sid, n): each live camera's next frame to serve

    def queue(sid, n):
        # a camera sends no frame at or after its eviction
        st = sched.streams[sid]
        if st.due(n) < st.t_evict:
            heapq.heappush(heap, (st.due(n), sid, n))

    for sid in sched.initial:
        queue(sid, 0)
    churn = list(sched.churn)
    waited = None                # when the next burst first waited
    origin = clock()
    while True:
        now = clock() - origin
        on_tick(now - preroll)
        while churn and churn[0][0] <= now:
            t_c, out, add = churn[0]
            if any(sid in out for _, sid, _ in heap):
                waited = now if waited is None else waited
                break
            churn.pop(0)
            for sid in out:
                eng.evict(sid)
            for sid in add:
                eng.admit(sid)
                queue(sid, 0)
            rec.churn.append((t_c, now, waited))
            waited = None
        # a burst still to apply may admit cameras with frames due in
        # the window
        if now >= end + drain_s or not churn and (not heap or (
                now >= end and heap[0][0] >= end)):
            break
        nxt = heap[0][0] if heap else math.inf
        if nxt > now:
            if churn:
                nxt = min(nxt, churn[0][0])
            with spans("wait_frames"):
                while clock() - origin < nxt:
                    left = nxt - (clock() - origin)
                    if left > 1e-3:
                        time.sleep(left - 5e-4)
            continue
        t_start = clock()
        with spans("generate"):
            frames, meta = {}, {}
            while heap and heap[0][0] <= now:
                due, sid, n = heapq.heappop(heap)
                if sid in frames:          # one frame per camera per tick
                    heapq.heappush(heap, (due, sid, n))
                    break
                if due >= end:             # past the window: not served
                    continue
                frames[sid] = make_frame(sched, pool, sid, n)
                # frames of the pre-roll are served but not measured
                meta[sid] = (n, origin + due if due >= preroll else None)
                queue(sid, n + 1)
        if now >= preroll:
            rec.gen_s.append(clock() - t_start)
        if frames:
            tick(eng, frames, rec, spans, t_start, meta,
                 timed=now >= preroll)
    streams = sched.streams
    attempted = sum(window_frames(st, preroll, end)
                    for st in streams.values())
    rec.dropped = attempted - len(rec.due)
    unserved = (sum(window_frames(streams[sid], preroll, end, n)
                    for _, sid, n in heap)
                + sum(window_frames(streams[sid], preroll, end)
                      for _, _, add in churn for sid in add))
    if rec.dropped != unserved:
        raise RuntimeError(f"chipbench: {attempted} frames due in the window, "
                           f"{len(rec.due)} served, but {unserved} unserved")
    return origin + preroll


def serve_closed(eng, sched, pool, rec: Record, seconds: float,
                 spans: Spans, on_tick=lambda now: None) -> float:
    """Closed loop: every camera fed at every tick until ``seconds``."""
    sids = list(sched.initial)
    n = 0
    t0 = clock()
    while clock() - t0 < seconds:
        on_tick(clock() - t0)
        t_start = clock()
        with spans("generate"):
            frames = {s: make_frame(sched, pool, s, n) for s in sids}
            meta = {s: (n, t_start) for s in sids}
        rec.gen_s.append(clock() - t_start)
        tick(eng, frames, rec, spans, t_start, meta)
        n += 1
    return t0


def warm_up(eng, sched, pool, fed_counts) -> None:
    """Compile (or load from the persistent cache) every program the
    window will run: the step, the churn flush, and the fed-row scatter
    at each fed count the traffic can produce. Served on throw-away
    cameras, which are evicted afterwards."""
    cap = eng.capacity
    ids = [("warm", i) for i in range(cap)]
    for sid in ids:
        eng.admit(sid)
    frame = pool[0]
    for f in fed_counts:
        h = eng.step({s: frame for s in ids[:f]}, block=False)
        h.result()
        fetch_served(eng)
    for sid in ids:
        eng.evict(sid)
    eng.admit(ids[0])       # an evict and an admit in one flush, as churn
    eng.state
    eng.evict(ids[0])
    jax.block_until_ready(eng.state)
