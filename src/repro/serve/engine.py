"""Multi-stream saccadic serving engine (DESIGN.md §5).

The paper's switched-cap readout is non-destructive precisely to enable
processing parallelism at <30 mW/MP; the backend half of that story is
serving MANY camera streams through ONE compiled program. This module
batches N independent streams through the compact saccade path
(`serve_step.make_saccade_step`'s exact per-frame semantics) in a single
jitted step:

* **Slots, not streams.** The engine owns ``capacity`` fixed slots; every
  device tensor is slot-major with a static leading axis, so the batched
  step is a pure function of ``(params, frames, fed, state)`` and
  compiles exactly once. Streams join/leave between frames via host-side
  bookkeeping (``admit`` / ``evict``) that only rewrites state rows —
  never shapes — so an admit→evict→admit cycle causes ZERO recompiles
  (asserted in tests via the engine's trace counter).

* **Partial-frame async steps** (DESIGN.md §12). ``step(frames)`` takes
  any SUBSET of the admitted streams — streams at different frame rates
  (a 30 Hz door camera next to a 7.5 Hz parking-lot camera) coexist in
  one engine. Which slots are fed this tick is a ``fed`` (S,) bool DATA
  argument of the same compiled program, so mixed-rate serving never
  retraces. An admitted-but-un-fed slot is a *hold*: its gaze state,
  frame age, temporal cache, and energy meters pass through bitwise
  unchanged (events accrue zero — the stream spent nothing this tick;
  the cache's droop clock advances once per SERVED frame, mirroring a
  dedicated per-stream loop), and the fed slots' outputs are bitwise
  identical to a full-cover step (per-slot independence; asserted in
  tests/test_serve_engine.py).

* **Fed-rows-only scatter ingest + coalesced churn** (DESIGN.md §12,
  §15). Frames live in a PERSISTENT device-resident ``(S, H, W, 3)``
  buffer: each tick uploads only the F fed rows (staged compactly on
  the host, one H2D copy of F·H·W·3 floats) and scatters them into the
  donated buffer with a tiny jitted ``at[slots].set`` — there is no
  full-capacity ``jnp.asarray(buf)`` per tick, so ingest bytes scale
  with the fed fraction exactly like every other per-tick cost.
  Un-fed rows keep the bytes of the last tick that fed them; their
  slots hold, so the stale payload never reaches state or logits.
  Admit/evict churn is continuously batched the same way:
  ``admit``/``evict`` only record host-side bookkeeping, and all
  pending row-writes (admit resets, evict flag-clears, governor budget
  re-splits) coalesce into ONE jitted flush right before the next step
  (or any state read) — k admits between two frames cost one device
  dispatch, not k.

* **Device-resident rollouts + async dispatch** (DESIGN.md §15).
  ``step_rollout(frames_by_tick)`` serves T ticks in ONE dispatch: a
  ``lax.scan`` (``serve_step.make_rollout``) carries the full
  :class:`StreamState` on device — indices, EMA, caches, meters,
  governor controls — with per-tick fed masks and frame payloads as
  scanned inputs, bitwise identical to T sequential ``step()`` calls in
  every engine mode. ``step(..., block=False)`` is the single-tick
  async path: it returns a :class:`StepHandle` over the device-resident
  logits, fetched lazily, so a caller (the fleet layer) can dispatch
  many engines before blocking on any.

* **Per-stream gaze state.** :class:`StreamState` carries each slot's
  current patch indices, an attention-score EMA (temporal smoothing of
  the saccade policy; ``ema_decay=0`` reproduces the single-stream step
  frame-for-frame), the frame age (age 0 ⇒ in-step bootstrap from the
  patch-energy proxy), and the slot-occupied flag.

* **In-step bootstrap.** Freshly admitted slots select their first gaze
  from the in-pixel energy proxy *inside* the batched step
  (``sensor_patches`` runs once and is forwarded to the compact forward
  via ``precomputed``), so admission needs no per-stream compiled
  bootstrap call and mixed-age batches stay one program.

* **Sharding.** With a mesh, the slot axis is sharded over the mesh's
  data axis via ``shard_map`` — the step is per-slot parallel with
  replicated params, so no collectives cross the slot axis. State
  buffers are donated, so steady-state serving is allocation-free on
  accelerators that support donation.

* **Energy metering** (DESIGN.md §10). Every step accumulates each
  slot's executed energy events (``aux["events"]`` from the compact
  forward: ADC conversions, cap charges, DAC loads, CDS, comparator and
  OpAmp windows) into per-slot cumulative meters in
  :class:`StreamState` — slot-major counts, donated and sharded like
  the rest of the state. ``engine.power_mw(sid)`` /
  ``engine.fleet_power_mw()`` price them with the calibrated
  :class:`repro.core.power.EnergyMeter`, so serving reports MEASURED
  frontend milliwatts, not the analytical steady-state assumption.

* **Power governor** (``governor=GovernorSpec(...)``, requires
  ``temporal=True``; `serve/governor.py`). Closes the loop on a chip
  mW budget: per-slot data knobs (recompute cap ``j_cap``, token tier
  ``k_eff``) are updated inside the jitted step from this frame's
  measured events and applied to the next frame's gate. Data, not
  shapes — a governed engine still compiles exactly once, and a slack
  budget is a bitwise no-op. Both knobs also bound the ragged kernels'
  per-slot row counts (DESIGN.md §11), so what the governor sheds is
  work the MXU never does and bytes VMEM never moves — not
  computed-then-masked tokens.

Use the engine when streams come and go or when one host serves many
cameras; use bare ``make_saccade_step`` for a single fixed-batch stream
(training-style evaluation, co-design sweeps).
"""

from __future__ import annotations

from typing import Any, Hashable, Mapping, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.power import EnergyMeter, EventCounts, dense_backend_macs
from repro.core.temporal import FeatureCache, init_feature_cache
from repro.models import backend_delta as bdel
from repro.serve import governor as gov_mod
from repro.serve import telemetry
from repro.serve.serve_step import make_rollout, saccade_scores


class StepHandle:
    """Non-blocking single-tick result (DESIGN.md §15).

    Holds the DEVICE-resident ``(S, n_classes)`` logits of one engine
    step plus the sid→slot map of the fed streams; :meth:`result`
    fetches them to the host (one blocking transfer) and caches the
    dict, so the fetch happens at most once and only when the caller
    actually wants the numbers. The handle stays valid across later
    engine calls — step outputs are fresh buffers, never donated — but
    holding many unfetched handles pins their logits in device memory;
    fetch (or drop) them within a tick or two. The fetch is the
    ``engine.result`` span of the step's tick (``tick``).
    """

    __slots__ = ("_logits", "_slots", "_out", "tick")

    def __init__(self, logits, slots: dict, tick: int | None = None):
        self._logits = logits
        self._slots = slots
        self._out = None
        self.tick = tick

    def result(self) -> dict[Hashable, np.ndarray]:
        """Block until the logits are on the host; stream id -> (n_classes,)
        logits for exactly the fed streams. Idempotent."""
        if self._out is None:
            arr = None
            if self._logits is not None:
                with telemetry.REGISTRY.span(telemetry.RESULT, self.tick):
                    arr = np.asarray(self._logits)
            self._out = {sid: arr[s] for sid, s in self._slots.items()}
            self._logits = None          # drop the device reference
        return self._out


class RolloutHandle:
    """Non-blocking rollout result: device-resident ``(T, S, n_classes)``
    logits plus the per-tick sid→slot maps; :meth:`result` fetches the
    whole rollout in ONE transfer and caches the per-tick dicts. Same
    lifetime contract as :class:`StepHandle`."""

    __slots__ = ("_logits", "_slot_maps", "_out")

    def __init__(self, logits, slot_maps: list):
        self._logits = logits
        self._slot_maps = slot_maps
        self._out = None

    def result(self) -> list[dict[Hashable, np.ndarray]]:
        """Block until the rollout's logits are on the host; one dict per
        tick (stream id -> (n_classes,) logits for that tick's fed
        streams). Idempotent."""
        if self._out is None:
            arr = None if self._logits is None else np.asarray(self._logits)
            self._out = [
                {sid: arr[t, s] for sid, s in m.items()}
                for t, m in enumerate(self._slot_maps)
            ]
            self._logits = None
        return self._out


class StreamState(NamedTuple):
    """Per-slot gaze state; every leaf is slot-major with static shape.

    ``cache`` is None unless the engine runs with ``temporal=True``, in
    which case it carries each slot's held-charge feature cache (incl.
    the per-patch age array driving the droop budget; DESIGN.md §6). The
    cache payload is stored in the digital wire format — int8 ADC codes
    (DESIGN.md §9) — so per-slot held state is 4x smaller than a float32
    cache; every mutation (step / admit wipe / freeze) preserves that
    dtype.

    ``events_last`` / ``events_mean`` are the per-slot energy meters
    (DESIGN.md §10): the events the slot's frontend executed on its last
    served frame, and the running per-frame MEAN since admit (inactive
    slots accrue nothing). The cumulative meter is a mean, not a sum, on
    purpose: counts stay at per-frame magnitude, so a week-long stream
    cannot saturate the float32 accumulator the way a monotone total
    would (increment < ulp ⇒ frozen meter); totals are derived as
    mean × frames at read time. Counts only — pricing happens at read
    time with the engine's :class:`EnergyMeter`, so recalibrating
    constants never touches device state. ``controls`` is the per-slot
    governor state (None unless the engine is governed).

    ``bcache`` is None unless the engine runs with ``backend_delta=True``
    (DESIGN.md §14): each slot's incremental-backend reuse state — the
    served wire rows it last computed on plus per-layer block outputs and
    cached logits/saliency — slot-major, wiped on admit, frozen on holds,
    exactly the ``cache`` playbook.
    """

    indices: jnp.ndarray    # (S, k) int32 — next frame's patch selection
    ema: jnp.ndarray        # (S, P) float32 — attention-score EMA
    frame_age: jnp.ndarray  # (S,) int32 — frames served since admit (0 = bootstrap)
    active: jnp.ndarray     # (S,) bool — slot occupied
    cache: FeatureCache | None = None   # per-slot temporal cache (temporal mode)
    events_last: EventCounts = EventCounts()    # (S,) leaves — last frame
    events_mean: EventCounts = EventCounts()    # (S,) leaves — mean/frame
    controls: gov_mod.GovernorControls | None = None  # governed mode only
    bcache: "bdel.BackendCache | None" = None  # backend-delta mode only (§14)


def _zero_events(capacity: int) -> EventCounts:
    return EventCounts(*(jnp.zeros((capacity,), jnp.float32)
                         for _ in EventCounts._fields))


def init_stream_state(
    cfg, capacity: int, temporal: bool = False, governed: bool = False,
    backend: bool = False,
) -> StreamState:
    """All slots free; indices are a placeholder (age 0 bootstraps in-step)."""
    k = cfg.frontend.n_active
    p = cfg.frontend.n_patches
    j_max = cfg.frontend.temporal.budget(k)
    return StreamState(
        indices=jnp.tile(jnp.arange(k, dtype=jnp.int32), (capacity, 1)),
        ema=jnp.zeros((capacity, p), jnp.float32),
        frame_age=jnp.zeros((capacity,), jnp.int32),
        active=jnp.zeros((capacity,), bool),
        cache=init_feature_cache(cfg.frontend, (capacity,)) if temporal else None,
        events_last=_zero_events(capacity),
        events_mean=_zero_events(capacity),
        controls=gov_mod.init_controls(capacity, j_max) if governed else None,
        # dtype from the ADC code wire — the same payload the FeatureCache
        # holds, so the two caches cannot disagree (§14)
        bcache=(bdel.init_backend_cache(
            cfg, k, batch_shape=(capacity,),
            dtype=cfg.frontend.adc.code_dtype) if backend else None),
    )


def _freeze_rows(act: jnp.ndarray, new, old):
    """Per-leaf ``where(active_slot, new, old)`` with act broadcast from
    (S,) up to each leaf's rank (slot-major leaves)."""
    def leaf(n, o):
        a = act.reshape(act.shape + (1,) * (n.ndim - 1))
        return jnp.where(a, n, o)

    return jax.tree.map(leaf, new, old)


def make_engine_step(cfg, explore: float = 0.1, ema_decay: float = 0.0,
                     project_fn=None, temporal: bool = False,
                     governor: "gov_mod.GovernorSpec | None" = None,
                     meter: EnergyMeter = EnergyMeter(),
                     frame_hz: float = 30.0, backend: bool = False):
    """Batched slot step:
    (params, frames (S,H,W,3), fed (S,) bool, state) -> (logits, state).

    Per slot this is exactly one ``make_saccade_step`` frame — same compact
    forward, same :func:`saccade_scores` policy — plus the engine-only
    pieces: in-step bootstrap at age 0, EMA blending of the scores, and
    freezing of inactive OR un-fed slots (their rows pass through
    unchanged and their logits are zeroed; DESIGN.md §12 hold semantics).
    ``fed`` is DATA: feeding any subset of the slots is the same compiled
    program. Pure and jit-stable: nothing here depends on which slots are
    occupied or fed except through ``state`` and ``fed`` values.

    With ``temporal=True`` the per-slot temporal cache (held-charge
    feature reuse, DESIGN.md §6) is threaded through ``state.cache``; a
    fresh slot's cache rows are invalidated in-step (belt to the admit
    reset, so a recycled slot can never serve its previous occupant's
    held features).

    Always metered (DESIGN.md §10): each slot's executed events land in
    ``state.events_last`` / fold into the running mean ``state.events_mean``
    (inactive slots accrue nothing). With ``governor`` given, the
    per-slot control knobs in ``state.controls`` are applied to this
    frame's gate (``stale_cap`` / ``k_cap`` — data, not shapes) and
    updated from this frame's measured events for the next.

    With ``backend=True`` the per-slot :class:`BackendCache` is threaded
    through ``state.bcache`` (DESIGN.md §14): tokens whose served wire
    row is bitwise unchanged reuse their cached backend work, and a
    frame whose whole selection held serves the cached logits/saliency
    outright with zero backend MACs. A governed engine feeds
    ``state.controls.eps`` in as the per-slot snap budget (the
    ``backend_eps`` knob of stage 3c) and hands the governor the dense
    backend's feed-forward mW estimate so the system floor accounts for
    the compute it can shed.
    """
    from repro.core import frontend as fe
    from repro.core import saliency as sal
    from repro.models.vit import vit_forward_compact

    fcfg = cfg.frontend
    k = fcfg.n_active
    j_max = fcfg.temporal.budget(k)
    n_pixels = float(fcfg.image_h * fcfg.image_w)
    backend_mw = 0.0
    if backend:
        # the governor's plant model for the backend: what a DENSE
        # backend frame costs at this frame rate — the delta path can
        # only spend less (measured events report what it actually did)
        backend_mw = (dense_backend_macs(
            k, cfg.n_layers, fcfg.patch.n_vectors, cfg.d_model,
            cfg.d_ff, cfg.n_classes)
            * meter.k.e_backend_mac_j * frame_hz * 1e3)

    def step(params, frames, fed, state: StreamState):
        # a slot advances only when it is occupied AND fed this tick —
        # un-fed slots are a data-only hold (DESIGN.md §12): every row
        # below passes through unchanged, exactly like an inactive slot
        act = state.active & fed
        # layer scopes (metadata only): a device trace attributes each op
        # to its layer through telemetry's scope map (DESIGN.md §15)
        with jax.named_scope("sensor"):
            # optics/mosaic/CDS once; forwarded to the compact forward below
            patches, weights = fe.sensor_patches(params["ip2"], frames, fcfg)
            boot = sal.topk_patch_indices(sal.patch_energy(patches), k)
            fresh = state.frame_age == 0
            indices = jnp.where(fresh[:, None], boot, state.indices)

        with jax.named_scope("frontend"):
            cache = None
            if temporal:
                cache = state.cache._replace(
                    valid=state.cache.valid & ~fresh[:, None]
                )
            bcache = eps = None
            if backend:
                # belt to the admit wipe, like the temporal cache above: a
                # fresh slot must never reuse its predecessor's activations
                bcache = state.bcache._replace(
                    valid=state.bcache.valid & ~fresh
                )
                if governor is not None:
                    eps = state.controls.eps
            k_cap = stale_cap = sign_mode = None
            if governor is not None:
                k_cap = gov_mod.tier_k_eff(governor, state.controls.tier, k)
                stale_cap = state.controls.j_cap
                if governor.sign_tier:
                    # ADC-less tier (DESIGN.md §13): a (S,) bool DATA knob —
                    # flagged slots serve the 1-bit sign view of the code
                    # wire and re-ledger conversions as sign comparisons;
                    # the cache keeps full-precision codes for recovery
                    sign_mode = gov_mod.tier_is_sign(governor,
                                                     state.controls.tier)
        # scoped inside: frontend, encoder (embed)
        logits, aux = vit_forward_compact(
            params, frames, cfg, indices=indices,
            project_fn=project_fn, precomputed=(patches, weights),
            cache=cache, k_cap=k_cap, stale_cap=stale_cap,
            sign_mode=sign_mode, backend_cache=bcache, backend_eps=eps,
            backend_act=act if backend else None,
        )
        with jax.named_scope("policy"):
            scores = saccade_scores(aux, explore)
            ema = jnp.where(
                fresh[:, None], scores,
                ema_decay * state.ema + (1.0 - ema_decay) * scores,
            )
            next_idx = sal.topk_patch_indices(ema, k)

        with jax.named_scope("meters"):
            # energy meters: only served slots spend events (held streams
            # accrue zero — they converted nothing this tick). The
            # cumulative meter is a RUNNING MEAN (Welford step over the
            # frames served since admit): per-frame magnitude, so
            # long-lived streams never freeze a float32 accumulator (see
            # StreamState)
            ev_last = EventCounts(*(
                jnp.where(act, e, o)
                for e, o in zip(aux["events"], state.events_last)
            ))
            n_served = (state.frame_age + 1).astype(jnp.float32)  # incl. this
            ev_mean = EventCounts(*(
                jnp.where(act, m + (e - m) / n_served, m)
                for m, e in zip(state.events_mean, ev_last)
            ))
            controls = None
            if governor is not None:
                controls = gov_mod.control_update(
                    governor, state.controls,
                    EventCounts(*(e * act.astype(jnp.float32)
                                  for e in aux["events"])),
                    act, meter, frame_hz,
                    n_pixels, fcfg.patch.pixels_per_patch,
                    fcfg.patch.n_vectors, j_max, k, backend_mw=backend_mw,
                )
            new_state = StreamState(
                indices=jnp.where(act[:, None], next_idx, state.indices),
                ema=jnp.where(act[:, None], ema, state.ema),
                frame_age=jnp.where(act, state.frame_age + 1,
                                    state.frame_age),
                active=state.active,
                cache=(_freeze_rows(act, aux["cache"], state.cache)
                       if temporal else None),
                events_last=ev_last,
                events_mean=ev_mean,
                controls=controls,
                bcache=(_freeze_rows(act, aux["backend_cache"], state.bcache)
                        if backend else None),
            )
            logits = jnp.where(act[:, None], logits, 0.0)
        return logits, new_state

    return step


def _make_churn(k: int, j_max: int, governed: bool):
    """ONE coalesced churn flush (DESIGN.md §12): every admit row-reset,
    evict flag-clear, and governor budget re-split that accumulated since
    the last step is applied in a single jitted call over *traced* (S,)
    hit masks — continuous batching of slot churn, one device dispatch
    per frame no matter how many streams joined or left between frames.

    ``admit_hit`` rows are fully reset (a recycled slot can never serve
    its previous occupant's state); ``evict_hit`` rows only drop the
    active flag (their stale rows are garbage until the next admit resets
    them, same as the old per-call evict). A slot admitted after an evict
    in the same window is just an admit (the reset supersedes the clear —
    host bookkeeping collapses the ops last-wins per slot)."""

    def churn(state: StreamState, admit_hit, evict_hit,
              budgets=None) -> StreamState:
        hit = admit_hit
        cache = state.cache
        if cache is not None:
            # full row wipe: a recycled slot starts with no held charge.
            # zeros_like keeps the code dtype — where(..., 0.0, int8) would
            # silently promote the wire-format cache to float32 (§9)
            cache = FeatureCache(
                features=jnp.where(
                    hit[:, None, None],
                    jnp.zeros((), cache.features.dtype), cache.features,
                ),
                energy=jnp.where(hit[:, None], 0.0, cache.energy),
                age=jnp.where(hit[:, None], 0, cache.age),
                valid=cache.valid & ~hit[:, None],
                n_stale=jnp.where(hit, 0, cache.n_stale),
            )
        bcache = state.bcache
        if bcache is not None:
            # same contract as the feature-cache wipe: dtype-preserving
            # broadcast zeroing, so a recycled slot can never serve its
            # previous occupant's activations (§14)
            bcache = bdel.wipe_rows(bcache, hit)
        wiped = EventCounts(*(jnp.where(hit, 0.0, e)
                              for e in state.events_last))
        wiped_mean = EventCounts(*(jnp.where(hit, 0.0, e)
                                   for e in state.events_mean))
        controls = state.controls
        if controls is not None:
            controls = gov_mod.reset_rows(controls, hit, j_max)
            if governed:
                controls = controls._replace(budget_mw=budgets)
        return StreamState(
            indices=jnp.where(hit[:, None],
                              jnp.arange(k, dtype=jnp.int32)[None], state.indices),
            ema=jnp.where(hit[:, None], 0.0, state.ema),
            frame_age=jnp.where(hit, 0, state.frame_age),
            active=(state.active & ~evict_hit) | hit,
            cache=cache,
            events_last=wiped,
            events_mean=wiped_mean,
            controls=controls,
            bcache=bcache,
        )

    return churn


class SaccadeEngine:
    """Slot-based multi-stream saccadic server.

    Host-side bookkeeping maps stream ids to slots; all device state lives
    in :class:`StreamState` and is only ever rewritten by two jitted pure
    functions (the batched step, and ONE coalesced churn flush batching
    every pending admit/evict/budget row-write — DESIGN.md §12), each
    compiled exactly once. ``n_traces`` counts retraces of the batched
    step — the zero-recompile contract is ``engine.n_traces == 1`` no
    matter how streams churn.

    ``step(frames)`` serves any SUBSET of the admitted streams (partial-
    frame async serving, DESIGN.md §12): streams at different frame rates
    coexist — un-fed slots hold bitwise (state frozen, zero events), fed
    slots are bitwise identical to a full-cover step. Which slots are fed
    is data, so mixed-rate serving stays one compile.

    ``engine.state`` is the inspection surface (reading it flushes any
    pending churn first), but its buffers are DONATED to the next
    step/churn call: always read through the attribute
    (``engine.state.frame_age[...]``), never hold a ``StreamState``
    reference across a mutation — on backends that implement donation
    (TPU/GPU) the held buffers are invalidated.

    Args:
      cfg: ViTConfig for the backend.
      params: model params (held by the engine; the step stays pure).
      capacity: number of slots (static batch of the compiled step).
      mesh: optional device mesh; the slot axis shards over ``axis`` via
        shard_map when capacity divides the axis size (else replicated).
      axis: mesh axis name for the slot dimension (default "data").
      explore / project_fn: as in ``make_saccade_step``.
      ema_decay: attention-EMA smoothing; 0.0 (default) = per-frame scores,
        matching the single-stream step exactly.
      temporal: enable the per-slot temporal delta gate (DESIGN.md §6) —
        each slot carries a held-charge :class:`FeatureCache` in
        ``state.cache``; only the stale subset of each frame's selection
        is re-projected/ADC-converted (``cfg.frontend.temporal`` sets the
        threshold/budget), and admit wipes the recycled slot's cache row.
      meter / frame_hz: the :class:`EnergyMeter` pricing the per-slot
        event meters and the sensor frame rate it prices at (DESIGN.md
        §10). Metering is always on; these only affect the readout.
      governor: a :class:`repro.serve.governor.GovernorSpec` — closes
        the loop on a chip mW budget (requires ``temporal=True``: the
        recompute cap is a knob of the temporal gate). Budget shares are
        priority-weighted over admitted streams (``admit(priority=...)``)
        and reallocated on every admit/evict (data-only row writes).
      backend_delta: thread a per-slot incremental-backend cache
        (:class:`repro.models.backend_delta.BackendCache`, DESIGN.md
        §14) through the step — tokens whose served wire row is bitwise
        unchanged reuse their cached backend work; a fully-held frame
        serves the cached logits with zero backend MACs. Pairs naturally
        with ``temporal=True`` (held charge is what holds the wire rows
        still) but is independent of it. A governed engine additionally
        drives the per-slot snap budget ``eps`` from the power loop when
        ``governor.backend_eps > 0`` (which *requires* this flag).
    """

    def __init__(self, cfg, params, capacity: int = 8, *, mesh=None,
                 axis: str = "data", explore: float = 0.1,
                 ema_decay: float = 0.0, project_fn=None,
                 temporal: bool = False,
                 meter: EnergyMeter = EnergyMeter(),
                 frame_hz: float = 30.0,
                 governor: "gov_mod.GovernorSpec | None" = None,
                 backend_delta: bool = False):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if governor is not None and not temporal:
            raise ValueError(
                "governor requires temporal=True: the recompute cap "
                "governs the temporal gate's per-frame allocation "
                "(DESIGN.md §10)"
            )
        if (governor is not None and governor.backend_eps > 0.0
                and not backend_delta):
            raise ValueError(
                "governor.backend_eps budgets the delta-gated backend "
                "(DESIGN.md §14); build the engine with "
                "backend_delta=True or drop backend_eps"
            )
        self.cfg = cfg
        self.params = params
        self.capacity = capacity
        self.mesh = mesh
        self.temporal = temporal
        self.backend = backend_delta
        self.meter = meter
        self.frame_hz = frame_hz
        self.governor = governor
        self._priority: dict[Hashable, float] = {}
        self._slots: list[Hashable | None] = [None] * capacity
        # cached sid -> slot map: the hot per-tick lookup (the list scan
        # in slot_of cost O(S) per fed stream per tick); maintained by
        # admit/evict, asserted == the slot list in tests
        self._slot_index: dict[Hashable, int] = {}
        self._n_traces = 0
        self._n_rollout_traces = 0
        # continuous batching of churn (DESIGN.md §12): slot -> "admit" |
        # "evict", last-op-wins; flushed in ONE jitted call before the
        # next step or state read
        self._pending: dict[int, str] = {}
        self._budgets_dirty = False
        self._budget_mw = None if governor is None else governor.budget_mw
        # fed-rows-only ingest (DESIGN.md §15): compact host staging for
        # the F fed rows (+ their slot ids) and the preallocated fed
        # mask, reused every tick — steady-state serving stages no fresh
        # host allocations
        self._stage = np.zeros(
            (capacity, cfg.frontend.image_h, cfg.frontend.image_w, 3),
            np.float32)
        self._stage_slots = np.zeros((capacity,), np.int32)
        self._fed = np.zeros((capacity,), bool)
        # H2D bytes of one fed row: its frame plus its slot id
        self._upload_row_bytes = (self._stage[0].nbytes
                                  + self._stage_slots.itemsize)
        # rollout staging, cached per distinct T (matching the one-trace-
        # per-T compile contract). Un-fed rows keep stale bytes from the
        # previous rollout of the same T — safe for the same reason the
        # per-tick path's persistent device buffer is: the scanned fed
        # mask gates every un-fed row out of the computation
        self._roll_stage: dict[int, tuple[np.ndarray, np.ndarray]] = {}

        fn = make_engine_step(cfg, explore=explore, ema_decay=ema_decay,
                              project_fn=project_fn, temporal=temporal,
                              governor=governor, meter=meter,
                              frame_hz=frame_hz, backend=backend_delta)

        self._slot_spec = P()
        if mesh is not None:
            from repro.launch.shardings import fit_spec

            spec = fit_spec(P(axis), (capacity,), mesh)
            # fit_spec replicates an indivisible axis by returning P(None) —
            # only shard_map when the slot axis actually survived
            if any(a is not None for a in spec):
                self._slot_spec = spec
                # per-slot parallel, params replicated — no collectives.
                # No varying-axes check: the Pallas kernels' outputs carry
                # no such annotation, and nothing here reduces over slots
                fn = shard_map(
                    fn, mesh=mesh,
                    in_specs=(P(), self._slot_spec, self._slot_spec,
                              self._slot_spec),
                    out_specs=(self._slot_spec, self._slot_spec),
                    check_vma=False,
                )

        def counted(params, frames, fed, state):
            # trace-time side effect: jit re-traces exactly once per compile,
            # so this counts compilations (the zero-recompile contract)
            self._n_traces += 1
            return fn(params, frames, fed, state)

        rollout = make_rollout(fn)

        def counted_rollout(params, frames_seq, fed_seq, state):
            # one trace PER DISTINCT T (the scan length is static);
            # reused Ts hit the jit cache — asserted in tests
            self._n_rollout_traces += 1
            return rollout(params, frames_seq, fed_seq, state)

        k = cfg.frontend.n_active
        self._step_fn = jax.jit(counted, donate_argnums=(3,))
        self._rollout_fn = jax.jit(counted_rollout, donate_argnums=(3,))
        self._churn_fn = jax.jit(
            _make_churn(k, cfg.frontend.temporal.budget(k),
                        governed=governor is not None),
            donate_argnums=(0,))

        def scatter(buf, rows, slots):
            # fed-rows-only ingest (DESIGN.md §15): (F, H, W, 3) staged
            # rows land in the donated persistent device frame buffer
            return buf.at[slots].set(rows)

        self._scatter_fn = jax.jit(scatter, donate_argnums=(0,))

        state = init_stream_state(cfg, capacity, temporal=temporal,
                                  governed=governor is not None,
                                  backend=backend_delta)
        # the persistent device frame buffer the scatter writes into and
        # the step reads from; sharded/placed like the slot-major state
        frames_dev = jnp.zeros(
            (capacity, cfg.frontend.image_h, cfg.frontend.image_w, 3),
            jnp.float32)
        if mesh is not None and self._slot_spec != P():
            sh = NamedSharding(mesh, self._slot_spec)
            state = jax.tree.map(lambda x: jax.device_put(x, sh), state)
            frames_dev = jax.device_put(frames_dev, sh)
        self._state = state
        self._frames_dev = frames_dev

    # ---- host-side slot bookkeeping ------------------------------------
    @property
    def state(self) -> StreamState:
        """Device state with any pending churn flushed first — the
        coalescing is invisible to readers."""
        self._flush_churn()
        return self._state

    @property
    def n_traces(self) -> int:
        return self._n_traces

    def compile_step(self):
        """Ahead-of-time compile the batched step for this engine's
        arguments and return the ``jax.stages.Compiled`` — its
        ``as_text()`` is the program the device runs. Shares jit's trace
        cache, so ``n_traces`` still counts one trace. Records the
        program's layer-scope map in :mod:`repro.serve.telemetry`."""
        self._flush_churn()
        compiled = self._step_fn.lower(
            self.params, self._frames_dev, jnp.asarray(self._fed),
            self._state).compile()
        telemetry.REGISTRY.record_scopes(compiled.as_text())
        return compiled

    @property
    def n_rollout_traces(self) -> int:
        """Compilations of the rollout program — one per DISTINCT rollout
        length T ever dispatched (T is static per compile; reused Ts hit
        the jit cache)."""
        return self._n_rollout_traces

    @property
    def stream_ids(self) -> list[Hashable]:
        return [s for s in self._slots if s is not None]

    @property
    def free_slots(self) -> int:
        return self._slots.count(None)

    def slot_of(self, stream_id: Hashable) -> int:
        try:
            return self._slot_index[stream_id]
        except KeyError:
            raise KeyError(f"stream {stream_id!r} not admitted") from None

    def admit(self, stream_id: Hashable, priority: float = 1.0) -> int:
        """Claim a free slot for a new stream; its first frame bootstraps
        from the in-pixel energy proxy inside the next step() call.
        ``priority`` weights the stream's share of a governed engine's
        power budget (ignored ungoverned). Host bookkeeping only — the
        device row-reset coalesces into the next churn flush."""
        if stream_id in self._slots:
            raise ValueError(f"stream {stream_id!r} already admitted")
        if priority <= 0:
            raise ValueError(f"priority must be > 0, got {priority}")
        try:
            slot = self._slots.index(None)
        except ValueError:
            raise RuntimeError(
                f"engine at capacity ({self.capacity}); evict a stream first"
            ) from None
        self._slots[slot] = stream_id
        self._slot_index[stream_id] = slot
        self._priority[stream_id] = float(priority)
        self._pending[slot] = "admit"
        self._budgets_dirty = True
        return slot

    def evict(self, stream_id: Hashable) -> None:
        slot = self.slot_of(stream_id)
        self._slots[slot] = None
        del self._slot_index[stream_id]
        self._priority.pop(stream_id, None)
        self._pending[slot] = "evict"        # last-op-wins per slot
        self._budgets_dirty = True

    def set_budget_mw(self, budget_mw: float) -> None:
        """Rewrite this engine's total power budget (the fleet layer's
        host-level knob, DESIGN.md §12): per-slot shares are re-split at
        the next churn flush — data-only, never a recompile."""
        if self.governor is None:
            raise RuntimeError("engine was built without a governor")
        if budget_mw <= 0:
            raise ValueError(f"budget_mw must be > 0, got {budget_mw}")
        self._budget_mw = float(budget_mw)
        self._budgets_dirty = True

    @property
    def budget_mw(self) -> float | None:
        """The engine-total power budget currently being split over slots
        (None when ungoverned)."""
        return self._budget_mw

    def _flush_churn(self) -> None:
        """Apply every pending admit/evict row-write (plus the governed
        budget re-split, DESIGN.md §10/§12) in ONE jitted call: the
        ``engine.churn_flush`` span, counting the rows it applies."""
        dirty_budget = self.governor is not None and self._budgets_dirty
        if not self._pending and not dirty_budget:
            return
        with telemetry.REGISTRY.span(telemetry.CHURN_FLUSH,
                                     count=len(self._pending)):
            admit_hit = np.zeros((self.capacity,), bool)
            evict_hit = np.zeros((self.capacity,), bool)
            for slot, op in self._pending.items():
                (admit_hit if op == "admit" else evict_hit)[slot] = True
            args = ()
            if self.governor is not None:
                w = np.zeros((self.capacity,), np.float64)
                for slot, sid in enumerate(self._slots):
                    if sid is not None:
                        w[slot] = self._priority[sid]
                args = (jnp.asarray(gov_mod.allocate_budgets(
                    self.governor, w, total_mw=self._budget_mw)),)
            self._state = self._churn_fn(
                self._state, jnp.asarray(admit_hit), jnp.asarray(evict_hit),
                *args)
        self._pending.clear()
        self._budgets_dirty = False

    # ---- serving -------------------------------------------------------
    def _stage_tick(self, frames: Mapping[Hashable, Any]
                    ) -> tuple[np.ndarray, dict[Hashable, int]]:
        """Stage one tick's frames for dispatch: validate ids, record the
        F fed rows compactly in the reused host staging buffers, and set
        the preallocated fed mask. Returns (fed mask view, sid->slot)."""
        fed = self._fed
        fed[:] = False
        slots_by_sid: dict[Hashable, int] = {}
        f = 0
        for sid, frame in frames.items():
            try:
                slot = self._slot_index[sid]
            except KeyError:
                unknown = set(frames) - self._slot_index.keys()
                raise ValueError(
                    f"frames for streams never admitted: "
                    f"unknown={sorted(map(str, unknown))}"
                ) from None
            self._stage[f] = frame          # f32 copy into the staging row
            self._stage_slots[f] = slot
            fed[slot] = True
            slots_by_sid[sid] = slot
            f += 1
        return fed, slots_by_sid

    def step(self, frames: Mapping[Hashable, Any], block: bool = True
             ) -> "dict[Hashable, np.ndarray] | StepHandle":
        """Serve one frame for any subset of the admitted streams.

        ``frames`` maps stream id -> (H, W, 3) RGB frame. Admitted
        streams without a frame this tick HOLD (partial-frame async
        serving, DESIGN.md §12): their per-stream clocks, gaze state,
        temporal cache, and meters do not advance, and the fed streams
        are served bitwise as if every stream had been fed. Unknown
        stream ids raise.

        Ingest uploads ONLY the fed rows (DESIGN.md §15): the F staged
        rows are one compact H2D copy scattered into the persistent
        donated device frame buffer — never a full-capacity upload.

        A call that dispatches is one tick of :mod:`repro.serve.telemetry`:
        an ``engine.step`` span holding ``engine.stage``,
        ``engine.churn_flush`` (when churn is pending), ``engine.upload``
        and ``engine.dispatch``; the handle's fetch is ``engine.result``.

        With ``block=True`` (default) returns stream id -> (n_classes,)
        logits for exactly the fed streams. With ``block=False`` the
        call returns as soon as the step is DISPATCHED: you get a
        :class:`StepHandle` over the device-resident logits and fetch
        them later via ``handle.result()`` — the async path that lets
        the fleet layer overlap many engines' device work (DESIGN.md
        §15). For T known ticks, prefer :meth:`step_rollout` — one
        dispatch instead of T.
        """
        if not frames:
            # nothing fed: all slots hold, no device dispatch
            return {} if block else StepHandle(None, {})
        tel = telemetry.REGISTRY
        tick = tel.new_tick()
        f = len(frames)
        with tel.span(telemetry.STEP, tick, count=1):
            with tel.span(telemetry.STAGE, count=f):
                fed, slots_by_sid = self._stage_tick(frames)
            self._flush_churn()
            with tel.span(telemetry.UPLOAD, count=f * self._upload_row_bytes):
                rows = jnp.asarray(self._stage[:f])
                slots = jnp.asarray(self._stage_slots[:f])
            with tel.span(telemetry.DISPATCH):
                self._frames_dev = self._scatter_fn(
                    self._frames_dev, rows, slots)
                logits, self._state = self._step_fn(
                    self.params, self._frames_dev, jnp.asarray(fed),
                    self._state)
        handle = StepHandle(logits, slots_by_sid, tick)
        return handle.result() if block else handle

    def step_rollout(self, frames_by_tick, block: bool = True
                     ) -> "list[dict[Hashable, np.ndarray]] | RolloutHandle":
        """Serve T ticks in ONE device dispatch (DESIGN.md §15).

        ``frames_by_tick`` is a sequence of T per-tick frame dicts, each
        exactly what :meth:`step` takes (any subset of the admitted
        streams; an empty dict is a legal all-hold tick). The whole
        closed saccade loop — selection, temporal gate, backend,
        governor control law, meters — runs device-resident under a
        ``lax.scan`` over the T ticks: logits and the final
        :class:`StreamState` are BITWISE identical to T sequential
        ``step()`` calls (tests/test_rollout.py), but the per-tick host
        round-trip (python staging, upload, dispatch, fetch) is paid
        once per rollout instead of once per tick.

        The stream cohort is fixed for the rollout: churn (admit/evict,
        budget re-splits) happens at rollout BOUNDARIES — pending churn
        flushes before dispatch, new ops apply to the next call. The
        governor's control law still runs per tick, in-scan. T is
        static per compile: each distinct T traces once
        (``n_rollout_traces``), reused Ts hit the jit cache.

        With ``block=True`` returns a list of T dicts (stream id ->
        logits for that tick's fed streams); ``block=False`` returns a
        :class:`RolloutHandle` fetching all T ticks in one transfer.
        """
        ticks = list(frames_by_tick)
        t_len = len(ticks)
        if t_len == 0:
            return [] if block else RolloutHandle(None, [])
        slot_maps: list[dict[Hashable, int]] = []
        for t, fr in enumerate(ticks):
            unknown = set(fr) - self._slot_index.keys()
            if unknown:
                raise ValueError(
                    f"tick {t}: frames for streams never admitted: "
                    f"unknown={sorted(map(str, unknown))}"
                )
            slot_maps.append({sid: self._slot_index[sid] for sid in fr})
        self._flush_churn()
        try:
            frames_seq, fed_seq = self._roll_stage[t_len]
        except KeyError:
            frames_seq = np.zeros((t_len,) + self._stage.shape, np.float32)
            fed_seq = np.zeros((t_len, self.capacity), bool)
            self._roll_stage[t_len] = (frames_seq, fed_seq)
        fed_seq[:] = False
        for t, fr in enumerate(ticks):
            for sid, frame in fr.items():
                slot = slot_maps[t][sid]
                frames_seq[t, slot] = frame
                fed_seq[t, slot] = True
        logits_seq, self._state = self._rollout_fn(
            self.params, jnp.asarray(frames_seq), jnp.asarray(fed_seq),
            self._state)
        handle = RolloutHandle(logits_seq, slot_maps)
        return handle.result() if block else handle

    def recompute_fraction(self, stream_id: Hashable) -> float:
        """Fraction of this stream's k selected patches that were actually
        re-projected/ADC-converted on its last served frame (temporal mode
        only). 1.0 on the bootstrap frame; drops toward 0 on static scenes
        as held charge serves the selection (DESIGN.md §6)."""
        if not self.temporal:
            raise RuntimeError("engine was built without temporal=True")
        slot = self.slot_of(stream_id)
        if int(self.state.frame_age[slot]) == 0:
            raise RuntimeError(
                f"stream {stream_id!r} has not served a frame yet"
            )
        # a governed slot only selects its tier's k_eff tokens, not the
        # static k — dividing by cfg n_active would understate recompute
        # on shed slots (e.g. 8 stale of a 16-token tier is 0.5, not 0.25)
        denom = (self.k_tier(stream_id) if self.governor is not None
                 else self.cfg.frontend.n_active)
        return float(self.state.cache.n_stale[slot]) / denom

    # ---- energy metering (DESIGN.md §10) -------------------------------
    def _fetch_meters(self, window: str) -> tuple[EventCounts, np.ndarray]:
        """ONE batched device->host fetch of (meter counts, frame ages) —
        every metering read costs exactly one sync no matter the slot
        count (asserted in tests/test_serve_engine.py)."""
        st = self.state
        src = st.events_last if window == "last" else st.events_mean
        host, ages = jax.device_get((src, st.frame_age))
        return (EventCounts(*(np.asarray(e) for e in host)),
                np.asarray(ages))

    def events(self, stream_id: Hashable, window: str = "last") -> EventCounts:
        """This stream's executed energy events: ``window="last"`` — the
        last served frame; ``"mean"`` — the per-frame mean since admit;
        ``"total"`` — cumulative since admit (derived as mean × frames in
        float64 at read time; the device meter stays at per-frame
        magnitude so it cannot saturate, see :class:`StreamState`)."""
        if window not in ("last", "mean", "total"):
            raise ValueError(
                f"window must be 'last', 'mean' or 'total', got {window!r}")
        slot = self.slot_of(stream_id)
        host, ages = self._fetch_meters(
            "last" if window == "last" else "mean")
        ev = EventCounts(*(float(e[slot]) for e in host))
        if window == "total":
            return ev.scale(float(ages[slot]))
        return ev

    def power_mw(self, stream_id: Hashable, window: str = "last") -> float:
        """MEASURED frontend power of this stream in mW, priced from its
        executed events by the engine's meter: ``window="last"`` — the
        last served frame's instantaneous power; ``"mean"`` — the average
        over every frame served since admit."""
        if window not in ("last", "mean"):
            raise ValueError(f"window must be 'last' or 'mean', got {window!r}")
        slot = self.slot_of(stream_id)
        host, ages = self._fetch_meters(window)
        if window == "mean" and ages[slot] == 0:
            raise RuntimeError(
                f"stream {stream_id!r} has not served a frame yet")
        return float(self.meter.power_mw(
            EventCounts(*(float(e[slot]) for e in host)), self.frame_hz))

    def fleet_power_mw(self, window: str = "last") -> float:
        """Measured frontend power summed over all admitted streams —
        the quantity a governed engine holds against its chip budget.
        Streams admitted but not yet served carry zero events and are
        skipped (they have no frame to average). Priced VECTORIZED over
        the slot axis from one batched fetch — O(1) syncs and one
        broadcast pricing pass regardless of capacity."""
        if window not in ("last", "mean"):
            raise ValueError(f"window must be 'last' or 'mean', got {window!r}")
        host, ages = self._fetch_meters(window)
        served = np.array(
            [s is not None for s in self._slots]) & (ages > 0)
        # EnergyMeter.power_mw is pure leaf arithmetic — (S,) counts in,
        # (S,) milliwatts out
        per_slot = np.asarray(self.meter.power_mw(host, self.frame_hz))
        return float(np.where(served, per_slot, 0.0).sum())

    def energy_report(self, stream_id: Hashable) -> dict:
        """Per-component joules this stream has spent since admit."""
        return self.meter.energy_j(
            self.events(stream_id, "total"), self.frame_hz)

    def recompute_cap(self, stream_id: Hashable) -> int:
        """The governor's current per-frame recompute allocation for this
        stream (governed engines only)."""
        if self.governor is None:
            raise RuntimeError("engine was built without a governor")
        return int(self.state.controls.j_cap[self.slot_of(stream_id)])

    def k_tier(self, stream_id: Hashable) -> int:
        """The governor's current active-token count for this stream
        (k_eff of its tier; governed engines only). The sign tier keeps
        the finest k tier's token count — it degrades the readout, not
        the selection (DESIGN.md §13)."""
        if self.governor is None:
            raise RuntimeError("engine was built without a governor")
        tier = int(self.state.controls.tier[self.slot_of(stream_id)])
        tokens = self.governor.tier_tokens(self.cfg.frontend.n_active)
        return tokens[min(tier, len(tokens) - 1)]

    def sign_readout(self, stream_id: Hashable) -> bool:
        """True while the governor holds this stream in the ADC-less
        sign-readout tier (DESIGN.md §13; governed engines only)."""
        if self.governor is None:
            raise RuntimeError("engine was built without a governor")
        tier = int(self.state.controls.tier[self.slot_of(stream_id)])
        return bool(self.governor.sign_tier
                    and tier >= len(self.governor.k_tiers))

    def backend_eps(self, stream_id: Hashable) -> float:
        """The governor's current backend snap budget for this stream
        (0.0 = exact reuse; DESIGN.md §14; governed backend-delta
        engines only)."""
        if self.governor is None:
            raise RuntimeError("engine was built without a governor")
        if not self.backend:
            raise RuntimeError("engine was built without backend_delta=True")
        return float(self.state.controls.eps[self.slot_of(stream_id)])

    def backend_cached(self, stream_id: Hashable) -> bool:
        """True when this stream's last served frame was answered entirely
        from its :class:`BackendCache` — zero backend MACs executed
        (DESIGN.md §14; backend-delta engines only)."""
        if not self.backend:
            raise RuntimeError("engine was built without backend_delta=True")
        slot = self.slot_of(stream_id)
        st = self.state
        if int(st.frame_age[slot]) == 0:
            raise RuntimeError(
                f"stream {stream_id!r} has not served a frame yet")
        return float(st.events_last.backend_macs[slot]) == 0.0

    def gaze(self, stream_id: Hashable) -> np.ndarray:
        """The (k,) patch indices this stream will ADC-convert next frame.

        Undefined before the stream's first frame — a fresh admit selects
        its first gaze from the in-pixel energy proxy *inside* the next
        step() call, so there is nothing to report yet (raises).
        """
        slot = self.slot_of(stream_id)
        if int(self.state.frame_age[slot]) == 0:
            raise RuntimeError(
                f"stream {stream_id!r} has not served a frame yet; its first "
                f"gaze is the in-step energy bootstrap of the next step()"
            )
        return np.asarray(self.state.indices[slot])
