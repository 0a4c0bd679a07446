"""Pallas TPU kernel: the sensor's optics and mosaic in one pass.

``(S, H, W, 3)`` float32 RGB -> ``(S, H, W)`` raw RGGB Bayer frame: the
reflect-padded separable Gaussian AA filter (paper §2.1.5) along W then
along H, and each site's own colour kept. The result is that of
``bayer.mosaic(bayer.antialias(rgb, cutoff, channels_last=True))``: every
product and sum is float32 on the VPU, in tap order.

On the TPU an ``(S, H, W, 3)`` array is planar (minor-to-major W, H, C,
S), so its ``(S, 3, H, W)`` transpose is free and each channel is a tiled
``H x W`` plane. One grid step takes one slot and a band of ``tb`` rows,
plus the 8-row tiles above and below it as the filter's halo:

1. The W pass, a lane roll per tap with the few lanes that fall off the
   frame taken from their reflection, runs on each channel 8 rows at a
   time. The column parity then keeps two planes instead of three: ``E``
   (what even rows need, R/G) and ``O`` (odd rows, G/B). Selecting a
   column before the H pass is exact: the H pass works within a column.
2. ``E`` and ``O`` of the band and its halo go to VMEM scratch; at the
   frame's top and bottom edge the halo rows are the reflected rows.
3. The H pass reads ``2r+1`` row-shifted windows of the scratch and each
   row keeps ``E`` or ``O`` by its parity.

HBM traffic is one read of the frame (plus 16 halo rows a band) and one
write of the Bayer frame; nothing is a contraction or a transpose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HALO = 8            # rows of one float32 sublane tile
BAND_ELEMS = 128 * 2048  # rows x columns of one band: 3 MiB of RGB


def band_rows(h: int, w: int) -> int:
    """Rows of one band: the largest multiple of 8 that divides ``h``
    within :data:`BAND_ELEMS`, or the whole frame where ``h`` is no
    multiple of 8 (then there is no halo)."""
    if h % HALO:
        return h
    best = HALO
    for tb in range(HALO, h + 1, HALO):
        if h % tb == 0 and tb * w <= BAND_ELEMS:
            best = tb
    return best


def _kernel(taps, cells, n_bands, main_ref, *refs):
    if n_bands > 1:
        top_ref, bot_ref, out_ref, e_ref, o_ref = refs
    else:
        out_ref, e_ref, o_ref = refs
    r = (len(taps) - 1) // 2
    tb, w = out_ref.shape
    rows = HALO if tb % HALO == 0 else tb     # rows of one chunk
    col_even = lax.broadcasted_iota(jnp.int32, (rows, w), 1) % 2 == 0
    (c00, c01), (c10, c11) = cells

    # the reflection can reach only the first and last lane tile: the taps
    # are summed over the whole width with wrapped rolls, and again over
    # the two edge tiles with the wrapped lanes replaced
    edge = 128 if w % 128 == 0 and w > 256 else w
    edge_lane = lax.broadcasted_iota(jnp.int32, (rows, edge), 1)

    def reflected(s, x, d, lo):
        """``s`` (lanes ``lo..lo+edge`` of x rolled by ``d``) with the
        lanes whose tap falls off the frame taken from their reflection."""
        for m in range(1, abs(d) + 1):
            dst = w - m if d > 0 else m - 1
            src = 2 * (w - 1) - dst - d if d > 0 else -(dst + d)
            if lo <= dst < lo + edge:
                s = jnp.where(edge_lane == dst - lo, x[:, src:src + 1], s)
        return s

    def w_pass(x):
        shifted = [x if t == r else pltpu.roll(x, (r - t) % w, 1)  # x[j+t-r]
                   for t in range(len(taps))]

        def tap_sum(window, lo=None):
            out = None
            for t, k in enumerate(taps):
                v = window(shifted[t])
                if lo is not None:
                    v = reflected(v, x, t - r, lo)
                out = v * k if out is None else out + v * k
            return out

        if edge == w:
            return tap_sum(lambda v: v, 0)
        full = tap_sum(lambda v: v)             # wrapped at the edges
        return jnp.concatenate(
            [tap_sum(lambda v: v[:, :edge], 0), full[:, edge:w - edge],
             tap_sum(lambda v: v[:, w - edge:], w - edge)], axis=1)

    def store_eo(get, at):
        wx = [w_pass(get(c)) for c in range(3)]
        e_ref[pl.ds(at, rows), :] = jnp.where(col_even, wx[c00], wx[c01])
        o_ref[pl.ds(at, rows), :] = jnp.where(col_even, wx[c10], wx[c11])

    def body(j, carry):
        y = pl.multiple_of(j * rows, rows)
        store_eo(lambda c: main_ref[c, pl.ds(y, rows), :], HALO + y)
        return carry

    lax.fori_loop(0, tb // rows, body, 0)
    b = pl.program_id(1)
    if n_bands > 1:
        store_eo(lambda c: top_ref[c], 0)
        store_eo(lambda c: bot_ref[c], HALO + tb)

    # at the frame's edges the halo is the reflection (row -m is row m)
    @pl.when(b == 0)
    def _():
        for ref in (e_ref, o_ref):
            for m in range(1, r + 1):
                ref[pl.ds(HALO - m, 1), :] = ref[pl.ds(HALO + m, 1), :]

    @pl.when(b == n_bands - 1)
    def _():
        last = HALO + tb - 1
        for ref in (e_ref, o_ref):
            for m in range(1, r + 1):
                ref[pl.ds(last + m, 1), :] = ref[pl.ds(last - m, 1), :]

    row_even = lax.broadcasted_iota(jnp.int32, (rows, w), 0) % 2 == 0
    for y in range(0, tb, rows):            # static: unaligned row windows
        acc = None
        for t, k in enumerate(taps):
            at = HALO + y + t - r
            # a chunk starts on an even row: bands and chunks are 8 rows
            # or whole
            v = jnp.where(row_even, e_ref[pl.ds(at, rows), :],
                          o_ref[pl.ds(at, rows), :]) * k
            acc = v if acc is None else acc + v
        out_ref[pl.ds(y, rows), :] = acc


@functools.partial(jax.jit,
                   static_argnames=("taps", "cells", "tb", "interpret"))
def bayer_frame_pallas(
    rgb: jnp.ndarray,
    taps: tuple[float, ...],
    cells: tuple[tuple[int, int], tuple[int, int]],
    tb: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``(S, H, W, 3)`` float32 -> ``(S, H, W)`` filtered Bayer frame.

    ``taps``: the ``2r+1`` filter taps (``r <= 8``, ``r < H, W``);
    ``cells``: the colour index of each site of the 2x2 unit cell;
    ``tb``: rows of one band (default :func:`band_rows`)."""
    s, h, w, _ = rgb.shape
    r = (len(taps) - 1) // 2
    if r > HALO or r >= min(h, w):
        raise ValueError(f"filter radius {r} for a {h}x{w} frame")
    tb = band_rows(h, w) if tb is None else tb
    if h % tb or (tb % HALO and tb != h):
        raise ValueError(f"a band of {tb} rows does not tile {h} rows")
    n_bands = h // tb
    planes = jnp.transpose(rgb, (0, 3, 1, 2))        # free on the TPU
    in_specs = [pl.BlockSpec((None, 3, tb, w), lambda i, b: (i, 0, b, 0))]
    args = [planes]
    if n_bands > 1:
        per = tb // HALO
        last = h // HALO - 1
        in_specs += [
            pl.BlockSpec((None, 3, HALO, w),
                         lambda i, b: (i, 0, jnp.maximum(b * per - 1, 0), 0)),
            pl.BlockSpec((None, 3, HALO, w),
                         lambda i, b: (i, 0, jnp.minimum((b + 1) * per, last),
                                       0)),
        ]
        args += [planes, planes]
    return pl.pallas_call(
        functools.partial(_kernel, taps, cells, n_bands),
        out_shape=jax.ShapeDtypeStruct((s, h, w), jnp.float32),
        grid=(s, n_bands),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, tb, w), lambda i, b: (i, b, 0)),
        scratch_shapes=[pltpu.VMEM((tb + 2 * HALO, w), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*args)
