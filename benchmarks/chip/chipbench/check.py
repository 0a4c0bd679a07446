"""The comparison that decides ``correct``.

Once the window has closed, the sampled cameras' whole histories are
replayed through the configuration's plain reference: the same frames,
and at each frame the gaze the server chose (served tokens, as a served
model's prompt and tokens are). Per served frame this gives the largest
logit gap to the reference, and how far the served gaze lies below the
reference's own choice from the frame before. The reference runs in
blocks of cameras so that it fits beside nothing else on the chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("logit_gap_max", "logit_gap_median", "gaze_gap_max")


def sample(sched, seed: int, n: int, seconds: float) -> set:
    """``n`` cameras drawn from the seed among those that serve in the
    window, always with the one that has the most frames due in it."""
    def due_count(st):
        if sched.loop == "closed":
            return 1
        end = min(st.t_evict, seconds)
        return max(0, math.ceil((end - st.t_admit - st.phase) * st.rate))

    sids = sorted(s for s, st in sched.streams.items() if due_count(st) > 0)
    longest = max(sids, key=lambda s: (due_count(sched.streams[s]), -s))
    rng = np.random.default_rng([seed, 0xC4EC])
    rest = [s for s in sids if s != longest]
    pick = rng.choice(rest, size=min(n - 1, len(rest)), replace=False)
    return {longest, *(int(s) for s in pick)}


def inputs(sched, rec, sids: list, k: int, n_classes: int,
           t_bucket: int) -> tuple[dict, np.ndarray, np.ndarray]:
    """(reference inputs with (T, B, ...) arrays, served logits (T, B,
    C), compared mask (T, B)) for the cameras ``sids``."""
    t_len = max(len(rec.out[s]) for s in sids)
    t_len = max(t_bucket, -(-t_len // t_bucket) * t_bucket)
    b = len(sids)
    xs = {"scene": np.zeros((t_len, b), np.int32),
          "box": np.zeros((t_len, b, 3), np.int32),
          "color": np.zeros((t_len, b, 3), np.float32),
          "gaze": np.tile(np.arange(k, dtype=np.int32), (t_len, b, 1)),
          "fed": np.zeros((t_len, b), bool)}
    logits = np.zeros((t_len, b, n_classes), np.float32)
    for j, sid in enumerate(sids):
        for t, (n, gaze, lg) in enumerate(rec.out[sid]):
            scene, box, colour = sched.frame_spec(sid, n)
            xs["scene"][t, j] = scene
            xs["box"][t, j] = box
            xs["color"][t, j] = colour
            xs["gaze"][t, j] = gaze
            xs["fed"][t, j] = True
            logits[t, j] = lg
    return xs, logits, xs["fed"].copy()


def replay(ref_mod, conf: dict, w: dict, pool, xs: dict, precision: str,
           block: int) -> tuple[np.ndarray, np.ndarray]:
    """Reference logits (T, B, C) and gaze gaps (T, B), ``block`` cameras
    at a time."""
    scenes = jnp.asarray(pool)
    fn = jax.jit(lambda w, sc, x: ref_mod.replay(conf, w, sc, x, precision))
    outs = []
    b = xs["fed"].shape[1]
    for lo in range(0, b, block):
        part = {kk: jnp.asarray(v[:, lo:lo + block]) for kk, v in xs.items()}
        pad = block - part["fed"].shape[1]
        if pad:
            part = {kk: jnp.concatenate(
                [v, jnp.zeros((v.shape[0], pad) + v.shape[2:], v.dtype)],
                axis=1) for kk, v in part.items()}
        lg, gap = fn(w, scenes, part)
        outs.append((np.asarray(lg)[:, :block - pad],
                     np.asarray(gap)[:, :block - pad]))
    return (np.concatenate([o[0] for o in outs], axis=1),
            np.concatenate([o[1] for o in outs], axis=1))


def numbers(served, ref, gaps, mask) -> dict:
    """The compared numbers over the frames in ``mask``."""
    per_frame = np.max(np.abs(served - ref), axis=-1)[mask]
    finite = bool(np.all(np.isfinite(served[mask])))
    if not finite or per_frame.size == 0:
        return {"logit_gap_max": math.inf, "logit_gap_median": math.inf,
                "gaze_gap_max": math.inf, "frames": int(per_frame.size)}
    return {"logit_gap_max": float(np.max(per_frame)),
            "logit_gap_median": float(np.median(per_frame)),
            "gaze_gap_max": float(np.max(gaps[mask])),
            "frames": int(per_frame.size)}


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit, and at least ``min_frames`` frames compared."""
    shown = {k: {"value": nums[k], "limit": limits[k]} for k in NUMBERS}
    shown["frames"] = {"value": nums["frames"], "limit": limits["min_frames"]}
    ok = (nums["frames"] >= limits["min_frames"]
          and all(nums[k] <= limits[k] for k in NUMBERS))
    return ok, shown
