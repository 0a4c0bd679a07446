"""Sustained-load fleet serving bench (DESIGN.md §12).

Drives the multi-host :class:`repro.serve.fleet.SaccadeFleet` the way
production traffic would: streams join and leave at rate λ (Poisson churn
through the per-host admit queues), with MIXED frame rates (30/15/7.5 Hz
→ frame periods 1/2/4 ticks, served as partial-frame async steps) and
mixed priority classes. Reports per-stream p50/p99 serve latency,
aggregate streams/s, the per-engine compile count (the fleet contract is
ONE trace per engine across all churn and rate skew), and the measured
fleet mW (DESIGN.md §10).

Methodology notes, mirrored by ``check_fleet_accounting.py``:

* Latency samples are per-tick wall times of ``fleet.step`` (a stream's
  serve latency — its frame is done when the tick's logits land on the
  host); the warm-up/compile ticks are excluded. Every tick is metered
  through the ASYNC path (DESIGN.md §15) and split into the
  non-blocking dispatch (staging + upload + launch across all fed
  hosts) and the blocking fetch (device compute + D2H), stored as
  separate per-sample fields whose sum IS the total serve sample. The
  raw samples ship in the artifact row so the smoke guard re-derives
  p50/p99 for all three series instead of trusting the stored
  percentiles.
* Fleet mW is priced from the per-slot MEAN event meters summed over the
  served streams; pricing is linear in the event counts, so the guard
  re-prices the stored summed counts with a fresh ``EnergyMeter`` and
  must land on the stored milliwatt figure exactly.
* Churn coalescing is counted live: every admit/evict between two frames
  must fold into at most one jitted churn flush per engine per tick.

Runs in a subprocess so XLA_FLAGS can force a multi-device CPU host
(2 hosts x 2 devices), like the §5 multistream sweep.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

# operating point shared with check_fleet_accounting.py's re-derivation
N_DEVICES = 4
N_HOSTS = 2
CAPACITY_PER_HOST = 32          # fleet capacity 64 = the acceptance floor
TICKS = 48
LAMBDA = 1.5                    # expected joins (= leaves) per tick
PERIODS = (1, 2, 4)             # mixed frame rates: 30 / 15 / 7.5 Hz
FRAME_HZ = 30.0
# sensor operating point (shared with the guard's event-law re-derivation)
IMAGE = 32
PATCH = 8
N_VECTORS = 16
ACTIVE_FRACTION = 0.25

_FLEET_CODE = """
    import json, time
    from repro.roofline.peaks import device_record
    import numpy as np
    import jax
    from repro.core.frontend import FrontendConfig
    from repro.core.power import EventCounts
    from repro.core.projection import PatchSpec
    from repro.core.temporal import TemporalSpec
    from repro.data.pipeline import SceneStream
    from repro.models.vit import ViTConfig, init_vit
    from repro.serve.fleet import SaccadeFleet, make_fleet_meshes
    from repro.serve.governor import GovernorSpec

    N_HOSTS = %(n_hosts)d
    CAP = %(cap)d
    TICKS = %(ticks)d
    LAM = %(lam)f
    PERIODS = %(periods)s
    FRAME_HZ = %(frame_hz)f

    # serving-rate operating point (small sensor, 1-layer backend): the
    # regime where host-side routing/ingest overhead is visible
    fcfg = FrontendConfig(image_h=%(image)d, image_w=%(image)d,
                          aa_cutoff=None,
                          patch=PatchSpec(patch_h=%(patch)d,
                                          patch_w=%(patch)d,
                                          n_vectors=%(n_vectors)d),
                          active_fraction=%(active_fraction)f,
                          temporal=TemporalSpec(delta_threshold=1e-4))
    cfg = ViTConfig(frontend=fcfg, n_layers=1, d_model=32, n_heads=2, d_ff=64)
    params = init_vit(jax.random.PRNGKey(0), cfg)
    stream = SceneStream(image=%(image)d)
    pool = stream.batch(0, 64)[0]

    meshes = make_fleet_meshes(N_HOSTS)
    fleet = SaccadeFleet(cfg, params, n_hosts=N_HOSTS, capacity=CAP,
                         meshes=meshes, temporal=True, frame_hz=FRAME_HZ,
                         governor=GovernorSpec(budget_mw=50.0))

    # count churn flushes per engine: k admits/evicts between two frames
    # must coalesce into <= 1 flush per engine per tick
    flushes = [0] * N_HOSTS
    for h, eng in enumerate(fleet.engines):
        inner = eng._churn_fn
        def wrap(inner=inner, h=h):
            def f(*a):
                flushes[h] += 1
                return inner(*a)
            return f
        eng._churn_fn = wrap()

    rng = np.random.default_rng(0)
    classes = ["realtime", "standard", "background"]
    period_of, phase_of = {}, {}
    next_id = 0
    churn_ops = 0

    def join(n):
        global next_id, churn_ops
        for _ in range(n):
            sid = f"s{next_id}"
            fleet.submit(sid, classes[next_id %% len(classes)])
            period_of[sid] = PERIODS[next_id %% len(PERIODS)]
            phase_of[sid] = next_id %% period_of[sid]
            next_id += 1
            churn_ops += 1

    join(N_HOSTS * CAP)                      # fill the fleet: 64 streams
    # warm-up ticks: drain queues, compile both engines, and absorb the
    # first post-compile executions (the first couple of calls after a
    # compile run slow on CPU; steady state is what we meter)
    frames = {sid: pool[i %% len(pool)]
              for i, sid in enumerate(period_of)}
    for _ in range(3):
        out = fleet.step(frames)
        for v in out.values():
            np.asarray(v)
    assert fleet.queued == 0 and fleet.free_slots == 0
    peak = len(fleet.stream_ids)

    samples_ms, dispatch_ms, fetch_ms = [], [], []
    served, fed_hist = 0, []
    t_wall0 = time.perf_counter()
    for t in range(TICKS):
        # lambda-churn: Poisson leaves then the same number of joins, so
        # the fleet stays saturated at 64 concurrent streams
        n_churn = int(rng.poisson(LAM))
        live = fleet.stream_ids
        for sid in rng.choice(live, size=min(n_churn, len(live) - 1),
                              replace=False):
            fleet.evict(str(sid))
            del period_of[str(sid)]; del phase_of[str(sid)]
            churn_ops += 1
        join(n_churn)
        base = {h: f for h, f in enumerate(flushes)}

        # mixed frame rates: only streams whose period divides this tick
        frames = {sid: pool[(hash(sid) + t) %% len(pool)]
                  for sid in list(period_of)
                  if sid in fleet._host_of
                  and t %% period_of[sid] == phase_of[sid]}
        # async split (DESIGN.md 15): meter the non-blocking dispatch
        # (staging + upload + launch, all hosts in flight) separately
        # from the blocking fetch (device compute + D2H). Total serve
        # latency is their sum by construction.
        t0 = time.perf_counter()
        handle = fleet.step(frames, block=False)
        t1 = time.perf_counter()
        out = handle.result()
        for v in out.values():
            np.asarray(v)                    # frames done when on host
        t2 = time.perf_counter()
        d_ms = (t1 - t0) * 1e3
        f_ms = (t2 - t1) * 1e3
        # queued joins admitted by this step serve from the NEXT tick;
        # count only what this tick actually served
        dispatch_ms.append(d_ms)
        fetch_ms.append(f_ms)
        samples_ms.append(d_ms + f_ms)
        served += len(out)
        fed_hist.append(len(out))
        peak = max(peak, len(fleet.stream_ids))
        for h in range(N_HOSTS):
            assert flushes[h] - base[h] <= 1, (h, flushes, base)
    t_wall = time.perf_counter() - t_wall0

    # fleet mW from the per-slot mean meters, plus the summed counts so
    # the smoke guard can re-price them (pricing is linear in events)
    fleet_mw = fleet.fleet_power_mw("mean")
    ev_sum = None
    for eng in fleet.engines:
        host, ages = eng._fetch_meters("mean")
        occ = np.array([s is not None for s in eng._slots]) & (ages > 0)
        s = [float(np.where(occ, np.asarray(leaf), 0.0).sum())
             for leaf in host]
        ev_sum = s if ev_sum is None else [a + b for a, b in zip(ev_sum, s)]

    print(json.dumps({
        "n_dev": len(jax.devices()),
        "samples_ms": samples_ms,
        "dispatch_ms": dispatch_ms,
        "fetch_ms": fetch_ms,
        "served_frames": served,
        "wall_s": t_wall,
        "peak_streams": peak,
        "churn_ops": churn_ops,
        "flushes": flushes,
        "n_traces": fleet.n_traces,
        "fed_min": min(fed_hist), "fed_max": max(fed_hist),
        "fleet_mw_mean": fleet_mw,
        "events_mean_sum": ev_sum,
        "event_fields": list(EventCounts._fields),
        "device": device_record(),
    }))
"""


def sustained_load(n_devices: int = N_DEVICES) -> list[dict]:
    """Run the λ-churn fleet simulation on forced multi-device CPU."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    code = _FLEET_CODE % {
        "n_hosts": N_HOSTS, "cap": CAPACITY_PER_HOST, "ticks": TICKS,
        "lam": LAMBDA, "periods": repr(list(PERIODS)),
        "frame_hz": FRAME_HZ, "image": IMAGE, "patch": PATCH,
        "n_vectors": N_VECTORS, "active_fraction": ACTIVE_FRACTION,
    }
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"fleet subprocess failed: {proc.stderr[-3000:]}")
    r = json.loads(proc.stdout.strip().splitlines()[-1])

    import numpy as np

    samples = np.asarray(r["samples_ms"])
    p50 = float(np.percentile(samples, 50))
    p99 = float(np.percentile(samples, 99))
    disp = np.asarray(r["dispatch_ms"])
    fetch = np.asarray(r["fetch_ms"])
    streams_per_s = r["served_frames"] / r["wall_s"]

    # hard contracts (data properties, never relaxed): one compile per
    # engine across all churn + rate skew; the fleet really saturated
    if any(n != 1 for n in r["n_traces"]):
        raise AssertionError(
            f"fleet engines recompiled under churn: n_traces={r['n_traces']}")
    assert r["peak_streams"] >= N_HOSTS * CAPACITY_PER_HOST, r["peak_streams"]
    assert r["fed_min"] < r["fed_max"], "frame rates did not actually mix"

    fleet_rec = {
        "source": "perf_counter+EnergyMeter",
        "n_hosts": N_HOSTS, "capacity_per_host": CAPACITY_PER_HOST,
        "ticks": TICKS, "lam": LAMBDA, "periods": list(PERIODS),
        "frame_hz": FRAME_HZ,
        "latency_ms_samples": r["samples_ms"],
        "dispatch_ms_samples": r["dispatch_ms"],
        "fetch_ms_samples": r["fetch_ms"],
        "p50_ms": p50, "p99_ms": p99,
        "dispatch_p50_ms": float(np.percentile(disp, 50)),
        "dispatch_p99_ms": float(np.percentile(disp, 99)),
        "fetch_p50_ms": float(np.percentile(fetch, 50)),
        "fetch_p99_ms": float(np.percentile(fetch, 99)),
        "served_frames": r["served_frames"], "wall_s": r["wall_s"],
        "streams_per_s": streams_per_s,
        "peak_streams": r["peak_streams"],
        "churn_ops": r["churn_ops"], "flushes": r["flushes"],
        "n_traces": r["n_traces"],
        "fleet_mw_mean": r["fleet_mw_mean"],
        "events_mean_sum": dict(zip(r["event_fields"],
                                    r["events_mean_sum"])),
    }
    rows = [{
        "name": f"fleet_sustained_s{N_HOSTS * CAPACITY_PER_HOST}"
                f"_h{N_HOSTS}_lam{LAMBDA:g}",
        "us_per_call": p50 * 1e3,
        "fleet": fleet_rec,
        "derived": (
            f"{r['peak_streams']} streams over {N_HOSTS} hosts, "
            f"lam={LAMBDA:g} churn x{r['churn_ops']} ops -> "
            f"{sum(r['flushes'])} flushes, mixed rates "
            f"{'/'.join(str(p) for p in PERIODS)}; p50 {p50:.2f}ms "
            f"p99 {p99:.2f}ms (dispatch p50 "
            f"{float(np.percentile(disp, 50)):.2f}ms / fetch p50 "
            f"{float(np.percentile(fetch, 50)):.2f}ms), "
            f"{streams_per_s:.0f} streams/s, "
            f"{r['fleet_mw_mean']:.3f} mW fleet, "
            f"traces {r['n_traces']}"
        ),
        "device": r["device"],           # the CPU child's
    }]
    return rows


def run() -> list[dict]:
    t0 = time.perf_counter()
    rows = sustained_load()
    dt = time.perf_counter() - t0
    rows.append({
        "name": "fleet_bench_wall",
        "us_per_call": dt * 1e6,
        "derived": f"sustained-load simulation wall {dt:.1f}s",
    })
    return rows
