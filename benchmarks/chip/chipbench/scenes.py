"""Seeded camera scenes of any H x W, generated in bulk on the host.

Each scene is a dark textured background (uniform in [0, b], with the
scene's light level b drawn in [0.1, 0.4]) with one bright shape (square, disc, cross or striped square, a quarter to an
eighth of the shorter side) at a random place: the procedural scenes of
``repro.data.pipeline.SceneStream``, generalised from square frames to
H x W and drawn with one call per scene pool instead of one per pixel
row. Intruders are bright squares painted over a scene, at the same
place in the host frame and in the reference's device frame.
"""

from __future__ import annotations

import numpy as np


def scene_pool(seed: int, n: int, h: int, w: int) -> np.ndarray:
    """(n, h, w, 3) float32 scenes from ``seed``."""
    rng = np.random.default_rng([seed, 0x5CE7E])
    imgs = rng.random((n, h, w, 3), dtype=np.float32)
    imgs *= rng.uniform(0.1, 0.4, size=(n, 1, 1, 1)).astype(np.float32)
    side = min(h, w)
    for i in range(n):
        cls = int(rng.integers(0, 4))
        size = int(rng.integers(side // 8, side // 4))
        cy = int(rng.integers(size, h - size))
        cx = int(rng.integers(size, w - size))
        color = rng.uniform(0.7, 1.0, size=3).astype(np.float32)
        y0, x0 = cy - size, cx - size
        yy, xx = np.mgrid[-size:size, -size:size]
        if cls == 0:
            m = np.ones_like(yy, bool)
        elif cls == 1:
            m = yy * yy + xx * xx < size * size
        elif cls == 2:
            m = (np.abs(yy) < size // 3) | (np.abs(xx) < size // 3)
        else:
            m = ((yy + y0 + size + xx + x0 + size) // 3) % 2 == 0
        imgs[i, y0:y0 + 2 * size, x0:x0 + 2 * size][m] = color
    return imgs


def paint(scene: np.ndarray, box, color) -> np.ndarray:
    """A copy of ``scene`` with a ``color`` square of side ``box[2]`` at
    row ``box[0]``, column ``box[1]``; the scene itself when the side is 0."""
    y0, x0, s = (int(v) for v in box)
    if s == 0:
        return scene
    out = scene.copy()
    out[y0:y0 + s, x0:x0 + s] = color
    return out
