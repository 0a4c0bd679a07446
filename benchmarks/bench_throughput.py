"""Paper Fig. 3 — processing rate vs weight lines C ∈ {1,2,4,8} for
720p/1080p sensors at 400/768 vectors per 32×32 patch, + the 8×8/192-vector
operating point. Reproduces the ~90 Hz 1080p C=2 claim and >30 Hz for 8×8,
and the 10x/30x data-dimensionality reduction (§1, §2.1.4).

Also sweeps the dense vs compact execution modes (DESIGN.md §3) over
active_fraction ∈ {1.0, 0.5, 0.25, 0.1}: wall time of the selectable
frontend compute (CDS patch voltages -> projection -> ADC readout; the
optics/mosaic stage integrates photons regardless of selection and is
excluded from both sides) and the streamed feature bytes vs full-frame raw.

Streamed-bytes methodology (DESIGN.md §9): every bytes figure is MEASURED
from the ``nbytes``/``itemsize`` of the actual wire arrays the frontend
emits (int8 ADC codes by default), never hand-computed from assumed bit
widths — rows carry a ``bytes`` record with ``source: "ndarray.nbytes"``
and the bench-smoke job re-derives them from a live frontend run
(benchmarks/check_bytes_accounting.py) to keep it that way.

The delta-gated backend sweep (DESIGN.md §14) crosses the same motion
levels with an eps reuse-budget grid at a backend-heavy operating point:
steady-state backend recompute fraction + worst-case logit error per cell,
a frontend/backend wall-time breakdown, and the tentpole claim — the
end-to-end gated step (frontend + fully-cached backend skip) beats the
dense step >= 2x on a static scene at eps=0.

And the multi-stream serving sweep (DESIGN.md §5): the slot-based
SaccadeEngine over 1/8/32 concurrent camera streams on forced multi-device
CPU (slot axis shard_map'd over 4 host devices where capacity divides),
streams/sec + per-stream latency per row, vs sequentially looping the
single-stream saccade step — asserts the batched engine wins ≥4x at 8
streams. Runs in a subprocess so XLA_FLAGS can force the device count.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import time

from repro.core.power import SensorConfig, data_reduction
from repro.core.throughput import figure3_sweep, frame_rate, rate_point

RAW_PIXEL_BITS = 10     # column SAR raw readout
FEATURE_BITS = 8        # edge-ADC feature samples (paper's 8-bit point)


def compact_operating_point(image: int = 256, patch: int = 16,
                            n_vectors: int = 400):
    """The compact-sweep frontend config — THE shared definition of the
    bench's operating point, also imported by check_bytes_accounting.py so
    the live bytes re-derivation can never drift from what the bench
    measured."""
    from repro.core.frontend import FrontendConfig
    from repro.core.projection import PatchSpec

    return FrontendConfig(
        image_h=image, image_w=image,
        patch=PatchSpec(patch_h=patch, patch_w=patch, n_vectors=n_vectors),
        aa_cutoff=None, active_fraction=0.25,
    )


def _best_of(f, *args, n: int = 7) -> float:
    """Best-of-n wall time in seconds for a jitted fn (CPU sim timing)."""
    import jax

    jax.tree_util.tree_leaves(f(*args))[0].block_until_ready()   # compile
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        out = f(*args)
        jax.tree_util.tree_leaves(out)[0].block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def compact_sweep(
    image: int = 256, patch: int = 16, n_vectors: int = 400, batch: int = 8
) -> list[dict]:
    """Dense-then-mask vs select->gather->project, same weights/selection."""
    import jax

    import repro.core as c
    from repro.core import saliency as sal
    from repro.core.frontend import (
        apply_frontend, project_readout, init_frontend_params,
    )

    base = compact_operating_point(image, patch, n_vectors)
    params = init_frontend_params(jax.random.PRNGKey(0), base)
    rgb = jax.random.uniform(jax.random.PRNGKey(1), (batch, image, image, 3))
    patches = c.extract_patches(c.mosaic(rgb), patch, patch)
    weights = c.strike_columns(params["a_rgb"], patch, patch)
    energy = c.patch_energy(patches)
    raw_bytes = image * image * RAW_PIXEL_BITS // 8

    # projection+readout is independent of active_fraction: one jitted fn
    # each (compact re-traces per k from the index shape; dense compiles once)
    dense = jax.jit(lambda pp, mm: sal.apply_patch_mask(
        project_readout(pp, weights, params, base, None), mm))
    compact = jax.jit(lambda pp, ii: project_readout(
        sal.gather_patches(pp, ii), weights, params, base, None))
    # the full wire-format step (select -> gather -> project -> encode):
    # what actually crosses the imager boundary, timed AND weighed
    # (re-traces per k via the index shape, like ``compact`` above)
    def make_wire(cfg, wire):
        def fn(pp, ii):
            return apply_frontend(
                params, None, cfg, indices=ii, mode="compact",
                precomputed=(pp, weights), wire=wire,
            ).features
        return jax.jit(fn)

    rows = []
    speedup_at_25 = None
    for af in (1.0, 0.5, 0.25, 0.1):
        cfg = dataclasses.replace(base, active_fraction=af)
        k = cfg.n_active
        mask = c.topk_patch_mask(energy, af)
        idx = c.topk_patch_indices(energy, k)

        t_dense = _best_of(dense, patches, mask)
        t_compact = _best_of(compact, patches, idx)
        speedup = t_dense / t_compact
        if af == 0.25:
            speedup_at_25 = speedup
        # measured wire traffic: nbytes of the actual emitted payload
        stream_bytes = int(make_wire(cfg, "codes")(patches, idx).nbytes) // batch
        rows.append({
            "name": f"frontend_dense_vs_compact_af{af:g}",
            "us_per_call": t_compact * 1e6,
            "bytes": {"measured_nbytes_per_frame": stream_bytes,
                      "source": "ndarray.nbytes"},
            "derived": (
                f"dense {t_dense * 1e3:.2f}ms compact {t_compact * 1e3:.2f}ms "
                f"{speedup:.2f}x; stream {stream_bytes / 1024:.0f}KiB "
                f"vs raw {raw_bytes / 1024:.0f}KiB "
                f"({raw_bytes / stream_bytes:.1f}x fewer bytes)"
            ),
        })

    # ADC-code-native wire (DESIGN.md §9) at the 25 % operating point:
    # measured nbytes + wall time, int8 codes vs the float32 compact wire
    idx25 = c.topk_patch_indices(energy, base.n_active)
    wire_code = make_wire(base, "codes")
    wire_float = make_wire(base, "float")
    codes_arr = wire_code(patches, idx25)
    float_arr = wire_float(patches, idx25)
    t_code = _best_of(wire_code, patches, idx25)
    t_float = _best_of(wire_float, patches, idx25)
    b_code = int(codes_arr.nbytes) // batch
    b_float = int(float_arr.nbytes) // batch
    byte_drop = b_float / b_code
    rows.append({
        "name": "wire_bytes_compact_af0.25",
        "us_per_call": t_code * 1e6,
        "bytes": {"measured_nbytes_per_frame": b_code,
                  "float32_nbytes_per_frame": b_float,
                  "source": "ndarray.nbytes"},
        "derived": (
            f"{codes_arr.dtype} wire {b_code / 1024:.0f}KiB/frame vs float32 "
            f"{b_float / 1024:.0f}KiB ({byte_drop:.1f}x fewer bytes measured); "
            f"code step {t_code * 1e3:.2f}ms vs float step {t_float * 1e3:.2f}ms"
        ),
    })
    # the wire claim is byte accounting, not wall clock: always hard
    assert byte_drop >= 3.5, (
        f"code wire only {byte_drop:.2f}x smaller than float32 measured")

    # the paper's streamed-bytes claim at its own operating point:
    # 2 Mpix / 32x32 / 400 vec / 25 % active, 8-bit features vs 10-bit raw
    op = SensorConfig()
    byte_reduction = data_reduction(op) * RAW_PIXEL_BITS / FEATURE_BITS
    rows.append({
        "name": "compact_streamed_bytes_reduction_paper_point",
        "us_per_call": 0.0,
        "derived": f"{byte_reduction:.1f}x vs full-frame raw (paper ~10x)",
    })
    # wall-clock asserts are meaningless on noisy shared runners; CI sets
    # IP2_BENCH_RELAX=1 to log instead of fail (byte accounting stays hard)
    if speedup_at_25 is None or speedup_at_25 < 2.0:
        msg = f"compact path only {speedup_at_25:.2f}x faster at 25% activity"
        if os.environ.get("IP2_BENCH_RELAX"):
            print(f"WARNING: {msg}", file=sys.stderr)
        else:
            raise AssertionError(msg)
    assert byte_reduction >= 10.0
    return rows


def motion_sweep(
    image: int = 512, patch: int = 32, n_vectors: int = 400, batch: int = 8,
    frames: int = 8,
) -> list[dict]:
    """Temporal delta gate (DESIGN.md §6) over motion levels.

    Three synthetic T-frame scenes — static (frozen frame), panning (the
    frame translates a few pixels per frame), full-motion (an unrelated
    scene every frame) — each served by the gated compact frontend with an
    unlimited recompute budget to measure the true per-frame recompute
    *demand* (stale fraction of the k selected patches) and the streamed
    feature bytes (held patches never leave the sensor).

    Wall time: the budget j is the hardware's provisioned per-frame
    conversion capacity. A static scene's steady demand is ~0, so j = k/8
    comfortably covers droop refresh + novelty; the gated step projecting
    j rows must beat the always-recompute step (k rows) by >= 2x. A
    full-motion scene needs j = k and the gate degenerates to the
    always-recompute path. Like the dense-vs-compact sweep, the timed
    quantity is the selectable frontend compute: the optics/mosaic stage
    and the in-pixel energy proxy run regardless of gating (photodiodes
    integrate light; the proxy is a free analog signal) and are excluded
    from both sides, and the weights are closed over as constants — the
    DAC is programmed once, not per frame.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.core as c
    from repro.core.frontend import (
        FrontendConfig, apply_frontend, init_frontend_params,
    )
    from repro.core.projection import PatchSpec
    from repro.core.temporal import TemporalSpec, init_feature_cache
    from repro.data.pipeline import SceneStream

    base = FrontendConfig(
        image_h=image, image_w=image,
        patch=PatchSpec(patch_h=patch, patch_w=patch, n_vectors=n_vectors),
        aa_cutoff=None, active_fraction=0.25,
        temporal=TemporalSpec(delta_threshold=2e-4),
    )
    params = init_frontend_params(jax.random.PRNGKey(0), base)
    k = base.n_active
    stream = SceneStream(image=image)
    frame0 = stream.batch(0, batch)[0]

    def scene_frames(kind: str) -> list:
        if kind == "static":
            return [frame0] * frames
        if kind == "panning":
            return [np.roll(frame0, 3 * t, axis=2) for t in range(frames)]
        return [stream.batch(t, batch)[0] for t in range(frames)]

    # --- recompute demand + streamed bytes per motion level (full API path,
    # budget None => j = k so the gate reports true per-frame demand)
    @jax.jit
    def demand_step(patches, weights, idx, cache):
        cf, cache = apply_frontend(
            params, None, base, indices=idx, mode="compact",
            precomputed=(patches, weights), cache=cache,
        )
        return cf.features, cache

    rows = []
    demand = {}
    for kind in ("static", "panning", "full_motion"):
        cache = init_feature_cache(base, (batch,))
        fracs, bytes_gated = [], 0
        row_nbytes = None
        t0 = time.perf_counter()
        for rgb in scene_frames(kind):
            patches, weights = c.sensor_patches(params, jnp.asarray(rgb), base)
            idx = c.topk_patch_indices(c.patch_energy(patches), k)
            feats, cache = demand_step(patches, weights, idx, cache)
            n_stale = np.asarray(cache.n_stale)
            fracs.append(float(n_stale.mean()) / k)
            # measured: bytes per converted row straight from the wire
            # payload the step emitted (int8 codes), not assumed bit math
            row_nbytes = int(feats.nbytes) // (batch * k)
            bytes_gated += int(n_stale.sum()) * row_nbytes
        dt = time.perf_counter() - t0
        bytes_always = frames * batch * k * row_nbytes
        steady = fracs[1:]
        demand[kind] = steady
        rows.append({
            "name": f"temporal_demand_{kind}",
            "us_per_call": dt / frames * 1e6,
            "bytes": {"measured_nbytes_per_frame": bytes_gated // frames,
                      "always_recompute_nbytes_per_frame": bytes_always // frames,
                      "source": "ndarray.nbytes"},
            "derived": (
                f"recompute fraction: frame0 {fracs[0]:.2f}, then "
                f"mean {sum(steady) / len(steady):.3f} max {max(steady):.3f}; "
                f"streamed {bytes_gated / 1024:.0f}KiB vs always-recompute "
                f"{bytes_always / 1024:.0f}KiB "
                f"({bytes_always / max(bytes_gated, 1):.1f}x fewer bytes)"
            ),
        })

    # --- wall time at provisioned capacity: j = k/8 (static-scene regime),
    # in the code wire end to end (DESIGN.md §9). Built from the gate's
    # primitives so the timed quantity stays the *selectable* frontend
    # compute: the energy proxy is precomputed (a free analog signal that
    # runs regardless of gating) and the weights are closed over (the DAC
    # is programmed once, not per frame) — same exclusions as PR 1/PR 3.
    from repro.core.frontend import project_wire
    from repro.core.saliency import gather_patches
    from repro.core.temporal import held_gain, select_stale, refresh, take_rows

    j = max(1, k // 8)
    spec_j = TemporalSpec(delta_threshold=2e-4, recompute_budget=j)
    patches, weights = c.sensor_patches(params, jnp.asarray(frame0), base)
    energy = c.patch_energy(patches)
    idx = c.topk_patch_indices(energy, k)

    @jax.jit
    def gated_tick(patches, energy, idx, cache):
        si, ne, ns = select_stale(
            energy, idx, cache, spec_j, base.patch.summer, base.adc)
        codes = project_wire(
            gather_patches(patches, si), weights, params, base, None, "codes")
        cache = refresh(cache, si, ne, codes, energy, ns)
        served = take_rows(cache.features, idx)          # int8 codes
        return served, held_gain(cache, idx, base.patch.summer), cache

    @jax.jit
    def always_tick(patches, idx):
        return project_wire(
            gather_patches(patches, idx), weights, params, base, None, "codes")

    cache = init_feature_cache(base, (batch,))
    for _ in range(frames):                  # converge to steady state
        *_, cache = gated_tick(patches, energy, idx, cache)

    t_gated = _best_of(gated_tick, patches, energy, idx, cache)
    t_always = _best_of(always_tick, patches, idx)
    speedup = t_always / t_gated
    held_payload, _, _ = gated_tick(patches, energy, idx, cache)
    rows.append({
        "name": "temporal_walltime_static_budget_k8",
        "us_per_call": t_gated * 1e6,
        "bytes": {
            # steady-state static scene: conversions track the true stale
            # count (droop refresh only) — measured from the emitted rows
            "measured_nbytes_per_frame":
                int(np.asarray(cache.n_stale).sum()) * n_vectors
                * held_payload.dtype.itemsize // batch,
            "always_recompute_nbytes_per_frame": int(held_payload.nbytes) // batch,
            "source": "ndarray.nbytes"},
        "derived": (
            f"always {t_always * 1e3:.2f}ms vs gated(j={j}/{k}) "
            f"{t_gated * 1e3:.2f}ms = {speedup:.2f}x on the static scene "
            f"({held_payload.dtype} wire)"
        ),
    })
    # demand sanity: the gate must be quiet on static scenes and saturated
    # on full motion — these are data properties, asserted hard
    assert max(demand["static"]) <= 0.10, demand["static"]
    assert sum(demand["full_motion"]) / len(demand["full_motion"]) >= 0.5
    if speedup < 2.0:
        msg = f"gated path only {speedup:.2f}x faster on the static scene"
        if os.environ.get("IP2_BENCH_RELAX"):
            print(f"WARNING: {msg}", file=sys.stderr)
        else:
            raise AssertionError(msg)
    return rows


def backend_delta_sweep(
    image: int = 128, patch: int = 16, frames: int = 8, batch: int = 2,
) -> list[dict]:
    """Delta-gated incremental backend (DESIGN.md §14) over motion levels
    and reuse budgets.

    A backend-heavy operating point (4-layer d128 encoder over 32 active
    tokens: ~25M backend MACs vs ~0.8M frontend MACs) served through the
    same three synthetic scenes as ``motion_sweep`` — static, panning,
    full-motion — crossed with an eps grid. Per cell: the steady-state
    backend recompute fraction (delta MACs / dense MACs, measured from the
    MAC meter the forward emits) and the worst-case logit error vs the
    dense encoder run on the SAME materialized wire block.

    Wall time is reported as a frontend/backend breakdown (gated frontend
    step, dense encoder, delta encoder on a warm cache) plus the
    end-to-end step comparison the tentpole claims: on a static scene at
    eps=0 the gated step (frontend + fully-cached backend skip) must beat
    the dense step (frontend + full encoder) by >= 2x. Selection is
    per-frame energy top-k — deterministic, so a static scene converges
    without the saccade policy in the loop (the engine-level policy path
    is exercised in tests/test_backend_delta.py).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.core as c
    from repro.core.frontend import FrontendConfig, apply_frontend
    from repro.core.projection import PatchSpec
    from repro.core.switched_cap import SummerSpec
    from repro.core.temporal import TemporalSpec, init_feature_cache
    from repro.data.pipeline import SceneStream
    from repro.models import vit as vit_mod
    from repro.models.backend_delta import delta_forward, init_backend_cache
    from repro.models.vit import ViTConfig, init_vit

    # passive droop-free summer: held wire rows are bitwise stable across
    # frames — the reuse precondition (DESIGN.md §14)
    fcfg = FrontendConfig(
        image_h=image, image_w=image,
        patch=PatchSpec(patch_h=patch, patch_w=patch, n_vectors=32,
                        summer=SummerSpec(mode="passive", hold_time_s=0.0)),
        aa_cutoff=None, active_fraction=0.5,
        temporal=TemporalSpec(delta_threshold=1e-3),
    )
    cfg = ViTConfig(frontend=fcfg, n_layers=4, d_model=128, n_heads=4,
                    d_ff=512)
    params = init_vit(jax.random.PRNGKey(0), cfg)
    k = fcfg.n_active
    stream = SceneStream(image=image)
    frame0 = stream.batch(0, batch)[0]

    def scene_frames(kind: str) -> list:
        if kind == "static":
            return [frame0] * frames
        if kind == "drift":
            # slow contrast creep (multiplicative — a DC offset would be
            # erased by CDS): every row is *slightly* stale each frame,
            # the regime the eps snap budget is built to absorb
            return [np.clip(frame0 * (1.0 + 0.005 * t), 0.0, 1.0)
                    .astype(np.float32) for t in range(frames)]
        if kind == "panning":
            return [np.roll(frame0, 3 * t, axis=2) for t in range(frames)]
        return [stream.batch(t, batch)[0] for t in range(frames)]

    @jax.jit
    def front_step(rgb, cache):
        patches, weights = c.sensor_patches(params["ip2"], rgb, fcfg)
        idx = c.topk_patch_indices(c.patch_energy(patches), k)
        return apply_frontend(params["ip2"], None, fcfg, indices=idx,
                              mode="compact", precomputed=(patches, weights),
                              cache=cache)

    def _embed(cf):
        return (vit_mod._embed_tokens(params, cf, cfg)
                + params["pos"][cf.indices])

    # standalone encoder programs over the materialized wire block — the
    # only formulation where eps=0 dense/delta equality is bitwise
    # (tests/test_backend_delta.py documents the XLA fusion-drift rationale)
    @jax.jit
    def dense_enc(cf):
        return vit_mod._encoder(params, _embed(cf), cfg, cf.valid)

    @jax.jit
    def delta_enc(cf, bc, eps):
        return delta_forward(params, cfg, cf, lambda: _embed(cf), bc, eps)

    wire_dtype = fcfg.adc.code_dtype
    rows = []
    frac = {}       # (kind, eps) -> steady-state mean recompute fraction
    err = {}        # (kind, eps) -> worst-case |delta - dense| logit error
    dense_macs = None
    kinds = ("static", "drift", "panning", "full_motion")
    for kind in kinds:
        for eps_val in (0.0, 1e-1, 5e-1):
            tcache = init_feature_cache(fcfg, (batch,))
            bc = init_backend_cache(cfg, k, (batch,), dtype=wire_dtype)
            eps = jnp.full((batch,), eps_val, jnp.float32)
            fr, er = [], 0.0
            for rgb in scene_frames(kind):
                cf, tcache = front_step(jnp.asarray(rgb), tcache)
                jax.block_until_ready(cf)
                ld, _ = dense_enc(cf)
                l, _, bc, macs = delta_enc(cf, bc, eps)
                if dense_macs is None:       # cold frame computes everything
                    dense_macs = float(np.asarray(macs).mean())
                fr.append(float(np.asarray(macs).mean()) / dense_macs)
                er = max(er, float(jnp.max(jnp.abs(l - ld))))
            frac[kind, eps_val] = sum(fr[1:]) / len(fr[1:])
            err[kind, eps_val] = er
        rows.append({
            "name": f"backend_delta_{kind}",
            "us_per_call": 0.0,
            # machine-readable record for check_backend_accounting.py:
            # MACs straight from the forward's MAC meter, never hand math
            "backend": {
                "dense_macs_per_frame": dense_macs,
                "recompute_frac": {f"{e:g}": frac[kind, e]
                                   for e in (0.0, 1e-1, 5e-1)},
                "max_logit_err": {f"{e:g}": err[kind, e]
                                  for e in (0.0, 1e-1, 5e-1)},
                "source": "mac-meter",
            },
            "derived": "; ".join(
                f"eps={e:g}: recompute {frac[kind, e]:.3f} "
                f"err {err[kind, e]:.2e}"
                for e in (0.0, 1e-1, 5e-1)
            ),
        })

    # the measured cold frame must reproduce the closed-form dense MAC
    # count — the same identity the engine's governor pricing relies on
    from repro.core.power import EnergyMeter, dense_backend_macs
    closed = dense_backend_macs(k, cfg.n_layers, fcfg.patch.n_vectors,
                                cfg.d_model, cfg.d_ff, cfg.n_classes)
    assert dense_macs == float(closed), (dense_macs, closed)

    # data properties, asserted hard: eps=0 is exact (same wire block,
    # standalone programs -> bitwise); a static scene fully caches; full
    # motion saturates; a larger eps never recomputes more; on the drift
    # scene the budget visibly trades recompute for bounded logit error
    assert all(err[kind, 0.0] == 0.0 for kind in kinds), err
    assert frac["static", 0.0] == 0.0, frac
    assert frac["full_motion", 0.0] >= 0.9, frac
    for kind in kinds:
        assert (frac[kind, 5e-1] <= frac[kind, 1e-1] + 1e-9
                <= frac[kind, 0.0] + 2e-9), (kind, frac)
    assert frac["drift", 5e-1] < frac["drift", 0.0], frac
    assert 0.0 < err["drift", 5e-1] <= 0.5, err

    # --- wall-time breakdown + the tentpole's end-to-end claim: converge
    # the caches on the static scene, then time the pieces and the
    # composed steps (the delta program must actually be on the skip path)
    tcache = init_feature_cache(fcfg, (batch,))
    bc = init_backend_cache(cfg, k, (batch,), dtype=wire_dtype)
    eps0 = jnp.zeros((batch,), jnp.float32)
    rgb0 = jnp.asarray(frame0)
    for _ in range(3):
        cf, tcache = front_step(rgb0, tcache)
        _, _, bc, macs = delta_enc(cf, bc, eps0)
    assert float(np.asarray(macs).sum()) == 0.0, "warm cache must fully skip"

    t_front = _best_of(front_step, rgb0, tcache)
    t_dense = _best_of(dense_enc, cf)
    t_delta = _best_of(delta_enc, cf, bc, eps0)
    t_e2e_dense = _best_of(lambda: dense_enc(front_step(rgb0, tcache)[0]))
    t_e2e_gated = _best_of(
        lambda: delta_enc(front_step(rgb0, tcache)[0], bc, eps0))
    speedup = t_e2e_dense / t_e2e_gated
    # backend milliwatts priced by the event meter's MAC constant at the
    # paper's 30 Hz serving point — re-derived live by the CI guard
    mw_30hz = dense_macs * EnergyMeter().k.e_backend_mac_j * 30.0 * 1e3
    rows.append({
        "name": "backend_walltime_breakdown_static",
        "us_per_call": t_e2e_gated * 1e6,
        "backend": {
            "dense_macs_per_frame": dense_macs,
            "dense_backend_mw_30hz": mw_30hz,
            "e2e_dense_ms": t_e2e_dense * 1e3,
            "e2e_gated_ms": t_e2e_gated * 1e3,
            "speedup": speedup,
            "source": "mac-meter",
        },
        "derived": (
            f"frontend {t_front * 1e3:.2f}ms, dense backend "
            f"{t_dense * 1e3:.2f}ms, delta backend (warm skip) "
            f"{t_delta * 1e3:.2f}ms"
        ),
    })
    rows.append({
        "name": "backend_delta_speedup_static_eps0",
        "us_per_call": t_e2e_gated * 1e6,
        "derived": (
            f"end-to-end dense {t_e2e_dense * 1e3:.2f}ms vs gated "
            f"{t_e2e_gated * 1e3:.2f}ms = {speedup:.2f}x on the static scene"
        ),
    })
    if speedup < 2.0:
        msg = f"gated backend step only {speedup:.2f}x on the static scene"
        if os.environ.get("IP2_BENCH_RELAX"):
            print(f"WARNING: {msg}", file=sys.stderr)
        else:
            raise AssertionError(msg)
    return rows


_MULTISTREAM_CODE = """
    import json, time
    from repro.roofline.peaks import device_record
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core.frontend import FrontendConfig
    from repro.core.projection import PatchSpec
    from repro.data.pipeline import SceneStream
    from repro.launch.mesh import make_host_mesh
    from repro.models.vit import ViTConfig, init_vit
    from repro.serve.engine import SaccadeEngine
    from repro.serve.serve_step import make_bootstrap_indices, make_saccade_step

    # serving-rate operating point: small sensor, 1-layer backend — the
    # regime where per-stream dispatch overhead (what slot batching
    # removes) is visible against per-frame compute
    fcfg = FrontendConfig(image_h=32, image_w=32, aa_cutoff=None,
                          patch=PatchSpec(patch_h=8, patch_w=8, n_vectors=16),
                          active_fraction=0.25)
    cfg = ViTConfig(frontend=fcfg, n_layers=1, d_model=32, n_heads=2, d_ff=64)
    params = init_vit(jax.random.PRNGKey(0), cfg)
    stream = SceneStream(image=32)
    n_dev = len(jax.devices())

    def best_of(f, n=15):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            f()
            best = min(best, time.perf_counter() - t0)
        return best

    out = {"n_dev": n_dev}
    rgb, _ = stream.batch(0, 32)

    # sequential baseline: loop the single-stream step, batch 1, 8 streams
    boot = jax.jit(make_bootstrap_indices(cfg))
    step = jax.jit(make_saccade_step(cfg))
    idx = [boot(params, jnp.asarray(rgb[i:i + 1])) for i in range(8)]

    def seq_tick():
        for i in range(8):
            logits, idx[i], _ = step(params, jnp.asarray(rgb[i:i + 1]), idx[i])
            np.asarray(logits)          # stream's frame is done when it lands on host
    seq_tick()                          # compile
    out["seq_8"] = best_of(seq_tick)

    # batched engine at 1 / 8 / 32 streams, plus the shard_map'd slot axis
    # at 32 (on real accelerators sharding divides the work; on forced host
    # devices it measures the emulation's transfer overhead)
    mesh = make_host_mesh(data=n_dev, model=1)
    for n, m in ((1, None), (8, None), (32, None), (32, mesh)):
        eng = SaccadeEngine(cfg, params, capacity=n, mesh=m)
        for s in range(n):
            eng.admit(s)
        frames = {s: rgb[s] for s in range(n)}
        eng.step(frames)                # compile + bootstrap frame
        key = f"engine_{n}" + ("_sharded" if m is not None else "")
        out[key] = best_of(lambda: eng.step(frames))
        out[key + "_traces"] = eng.n_traces

    out["device"] = device_record()
    print(json.dumps(out))
"""


def multistream_sweep(n_devices: int = 4) -> list[dict]:
    """Engine vs sequential-loop serving on forced multi-device CPU."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_MULTISTREAM_CODE)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"multistream subprocess failed: {proc.stderr[-3000:]}")
    r = json.loads(proc.stdout.strip().splitlines()[-1])

    rows = []
    for key, n in (("engine_1", 1), ("engine_8", 8), ("engine_32", 32),
                   ("engine_32_sharded", 32)):
        t = r[key]
        sharded = key.endswith("_sharded")
        rows.append({
            "name": f"multistream_{key.replace('engine_', 'engine_s')}",
            "us_per_call": t * 1e6,
            "derived": (
                f"{n / t:.0f} streams/s, {t * 1e3:.2f}ms/frame per-stream "
                f"latency, {r[key + '_traces']} compile(s)"
                + (f", slot axis shard_map'd over {r['n_dev']} host devices"
                   if sharded else "")
            ),
        })
    t_seq, t_eng = r["seq_8"], r["engine_8"]
    speedup = t_seq / t_eng
    rows.append({
        "name": "multistream_seq_loop_s8",
        "us_per_call": t_seq * 1e6,
        "derived": f"{8 / t_seq:.0f} streams/s looping the single-stream step",
    })
    rows.append({
        "name": "multistream_batched_speedup_s8",
        "us_per_call": t_eng * 1e6,
        "derived": f"{speedup:.2f}x streams/s, batched engine vs sequential loop",
    })
    for row in rows:
        row["device"] = r["device"]      # the CPU child's, not this process's
    traces = {k: v for k, v in r.items() if k.endswith("_traces")}
    if any(v != 1 for v in traces.values()):
        raise AssertionError(f"engine recompiled during steady-state serving: {traces}")
    if speedup < 4.0:
        msg = f"batched engine only {speedup:.2f}x vs sequential loop at 8 streams"
        if os.environ.get("IP2_BENCH_RELAX"):
            print(f"WARNING: {msg}", file=sys.stderr)
        else:
            raise AssertionError(msg)
    return rows


def run() -> list[dict]:
    t0 = time.perf_counter_ns()
    sweep = figure3_sweep()
    us = (time.perf_counter_ns() - t0) / 1e3
    rows = []
    for p in sweep:
        rows.append({
            "name": f"fig3_{p.fmt}_{p.n_vectors}vec_C{p.c_lines}",
            "us_per_call": us / len(sweep),
            "derived": f"{p.frame_hz:.1f}Hz {p.mpix_per_s:.0f}Mpix/s",
        })
    op = rate_point("1080p", 2, 32, 400)
    rows.append({
        "name": "fig3_operating_point_1080p_C2_400vec",
        "us_per_call": us, "derived": f"{op.frame_hz:.1f}Hz (paper ~90Hz)",
    })
    hz8 = frame_rate(8, 192, 2)
    rows.append({
        "name": "fig3_8x8_192vec", "us_per_call": us,
        "derived": f"{hz8:.0f}Hz (paper >30Hz)",
    })
    red = data_reduction(SensorConfig())
    red_rgb = data_reduction(SensorConfig(), vs_rgb=True)
    rows.append({"name": "data_reduction_vs_bayer", "us_per_call": us,
                 "derived": f"{red:.1f}x (paper 10x)"})
    rows.append({"name": "data_reduction_vs_rgb", "us_per_call": us,
                 "derived": f"{red_rgb:.1f}x (paper 30x)"})
    assert 85 <= op.frame_hz <= 95 and hz8 > 30 and red >= 10 and red_rgb >= 30
    # the sweeps are independent experiments: collect every row we can,
    # then fail loudly — one sweep's assert must not erase the others'
    # rows from the artifact (run.py keeps ``e.rows`` on failure)
    failures = []
    for sweep in (compact_sweep, motion_sweep, backend_delta_sweep,
                  multistream_sweep):
        try:
            rows.extend(sweep())
        except Exception as e:
            failures.append(f"{sweep.__name__}: {type(e).__name__}: {e}")
    if failures:
        err = AssertionError("; ".join(failures))
        err.rows = rows
        raise err
    return rows
