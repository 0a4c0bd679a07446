"""The comparison that decides ``correct``.

Once the window has closed, the sampled cameras' whole histories are
replayed through the configuration's plain reference: the same frames,
and at each frame the gaze the server chose (served tokens, as a served
model's prompt and tokens are). The reference gives, per served frame,
its output (a pytree of arrays, as the engine's result is) and how far
the served gaze lies below its own choice from the frame before; the
configuration's system compares the two (``NUMBERS``, ``compare``). The
reference runs in blocks of cameras so that it fits beside nothing else
on the chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


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


def inputs(sched, rec, sids: list, k: int,
           t_bucket: int) -> tuple[dict, object, np.ndarray]:
    """(reference inputs with (T, B, ...) arrays, served outputs with
    (T, B, ...) leaves in the structure of the first recorded output, or
    None where none was recorded, compared mask (T, B)) for the cameras
    ``sids``."""
    t_len = max(len(rec.out[s]) for s in sids)
    t_len = max(t_bucket, -(-t_len // t_bucket) * t_bucket)
    b = len(sids)
    xs = {"scene": np.zeros((t_len, b), np.int32),
          "box": np.zeros((t_len, b, 3), np.int32),
          "color": np.zeros((t_len, b, 3), np.float32),
          "gaze": np.tile(np.arange(k, dtype=np.int32), (t_len, b, 1)),
          "fed": np.zeros((t_len, b), bool)}
    first = [o for s in sids for _, _, o in rec.out[s][:1]]
    if not first:
        return xs, None, xs["fed"].copy()
    leaves, tree = jax.tree.flatten(first[0])
    bufs = [np.zeros((t_len, b) + x.shape, x.dtype) for x in leaves]
    for j, sid in enumerate(sids):
        for t, (n, gaze, out) in enumerate(rec.out[sid]):
            scene, box, colour = sched.frame_spec(sid, n)
            xs["scene"][t, j] = scene
            xs["box"][t, j] = box
            xs["color"][t, j] = colour
            xs["gaze"][t, j] = gaze
            xs["fed"][t, j] = True
            for buf, x in zip(bufs, jax.tree.leaves(out)):
                buf[t, j] = x
    return xs, jax.tree.unflatten(tree, bufs), xs["fed"].copy()


def replay(ref_mod, conf: dict, w: dict, pool, xs: dict, precision: str,
           block: int) -> tuple[object, np.ndarray]:
    """Reference outputs (a pytree of (T, B, ...) leaves) and gaze gaps
    (T, B), ``block`` cameras at a time."""
    scenes = jnp.asarray(pool)
    fn = jax.jit(lambda w, sc, x: ref_mod.replay(conf, w, sc, x, precision))
    outs, gaps = [], []
    b = xs["fed"].shape[1]
    for lo in range(0, b, block):
        part = {kk: jnp.asarray(v[:, lo:lo + block]) for kk, v in xs.items()}
        pad = block - part["fed"].shape[1]
        if pad:
            part = {kk: jnp.concatenate(
                [v, jnp.zeros((v.shape[0], pad) + v.shape[2:], v.dtype)],
                axis=1) for kk, v in part.items()}
        out, gap = fn(w, scenes, part)
        outs.append(jax.tree.map(lambda x: np.asarray(x)[:, :block - pad],
                                 out))
        gaps.append(np.asarray(gap)[:, :block - pad])
    return (jax.tree.map(lambda *p: np.concatenate(p, axis=1), *outs),
            np.concatenate(gaps, axis=1))


def check_limits(limits: dict, names) -> None:
    """Refuse a checks file that lacks a limit for one of the system's
    compared numbers, or the least number of frames compared."""
    missing = sorted((set(names) | {"min_frames"}) - set(limits))
    if missing:
        raise SystemExit(f"chipbench: the checks file has no limit for "
                         f"{missing}")


def compare(system, served, ref, gaps, mask) -> dict:
    """The system's compared numbers; each infinite, over no frames,
    where no sampled frame was served."""
    if served is None:
        return {**dict.fromkeys(system.NUMBERS, math.inf), "frames": 0}
    return system.compare(served, ref, gaps, mask)


def verdict(nums: dict, limits: dict, names) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): each of the numbers
    ``names`` at or under its limit, and at least ``min_frames`` frames
    compared."""
    shown = {k: {"value": nums[k], "limit": limits[k]} for k in names}
    shown["frames"] = {"value": nums["frames"], "limit": limits["min_frames"]}
    ok = (nums["frames"] >= limits["min_frames"]
          and all(nums[k] <= limits[k] for k in names))
    return ok, shown
