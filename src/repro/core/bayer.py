"""Bayer mosaic + anti-aliasing model (paper §2.1.5).

The HW sensor produces a raw mosaiced Bayer image (RGGB); no demosaicing is
performed in hardware. The trained RGB projection matrix A is transformed
to A' by *striking out the columns* of A that have no corresponding element
in the Bayer vector — i.e. each pixel site keeps only its own color's
weight column.

Anti-aliasing: micro-lenses give near-unity fill factor; the combined
optics are modelled as Gaussian low-pass filters with -3 dB cutoff at 0.5
or 0.25 of Nyquist. The paper reports training accuracy is virtually
unaffected even at 0.25 Nyquist (slight defocus is a good AA filter).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# RGGB unit cell: channel index at (row%2, col%2)
RGGB = ((0, 1), (1, 2))  # R G / G B


def bayer_channel_map(h: int, w: int) -> jnp.ndarray:
    """(H, W) int32 array of the color-channel index of each pixel site."""
    rows = jnp.arange(h)[:, None] % 2
    cols = jnp.arange(w)[None, :] % 2
    cell = jnp.asarray(RGGB, dtype=jnp.int32)
    return cell[rows, cols]


def mosaic(rgb: jnp.ndarray) -> jnp.ndarray:
    """(..., H, W, 3) RGB -> (..., H, W) raw Bayer frame: each site keeps
    its own colour's channel, chosen by row and column parity. A masked
    sum over C adds exact zeros, so it is the selection bit for bit."""
    h, w = rgb.shape[-3], rgb.shape[-2]
    row = jnp.arange(h)[:, None, None] % 2
    col = jnp.arange(w)[None, :, None] % 2
    (c00, c01), (c10, c11) = RGGB
    site = jnp.where(row == 0, jnp.where(col == 0, c00, c01),
                     jnp.where(col == 0, c10, c11))          # (H, W, 1)
    keep = site == jnp.arange(rgb.shape[-1])
    return jnp.sum(jnp.where(keep, rgb, 0.0), axis=-1)


def strike_columns(a_rgb: jnp.ndarray, patch_h: int, patch_w: int) -> jnp.ndarray:
    """Trained matrix A (M, N²·3) -> A' (M, N²) for the Bayer sensor.

    For pixel site i with Bayer color c(i), keep only column (i, c(i)) of
    the vectorized-RGB matrix; all other color columns have no corresponding
    hardware element and are struck out (paper §2.1.5).
    """
    m, n2x3 = a_rgb.shape
    n2 = patch_h * patch_w
    if n2x3 != n2 * 3:
        raise ValueError(f"A has {n2x3} cols, expected {n2 * 3}")
    ch = bayer_channel_map(patch_h, patch_w).reshape(-1)  # (N²,)
    a = a_rgb.reshape(m, n2, 3)
    return jnp.take_along_axis(a, ch[None, :, None], axis=-1)[..., 0]


def gaussian_kernel_1d(cutoff_nyquist: float, radius: int | None = None) -> jnp.ndarray:
    """1-D Gaussian whose magnitude response is -3 dB at cutoff·Nyquist.

    |H(f)| = exp(-2 (pi sigma f)^2); solving |H(fc)|² = 1/2 at
    fc = cutoff·0.5 cycles/px gives sigma = sqrt(ln 2)/(2 pi fc) / sqrt(2).
    """
    fc = cutoff_nyquist * 0.5  # cycles / pixel
    sigma = math.sqrt(math.log(2.0) / 2.0) / (2.0 * math.pi * fc)
    if radius is None:
        radius = max(1, int(math.ceil(3.0 * sigma)))
    x = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    k = jnp.exp(-0.5 * (x / sigma) ** 2)
    return k / jnp.sum(k)


def aa_taps(cutoff_nyquist: float) -> tuple[float, ...]:
    """The AA filter's taps as static float32 constants of a program."""
    with jax.ensure_compile_time_eval():
        return tuple(float(t) for t in gaussian_kernel_1d(cutoff_nyquist))


def _blur_axis(x: jnp.ndarray, taps: tuple[float, ...], axis: int) -> jnp.ndarray:
    """One pass of the separable filter along ``axis``: reflect padding,
    then each tap as a static shifted slice, summed in tap order."""
    r = (len(taps) - 1) // 2
    n = x.shape[axis]
    pad = [(0, 0)] * x.ndim
    pad[axis] = (r, r)
    xp = jnp.pad(x, pad, mode="reflect")
    out = lax.slice_in_dim(xp, 0, n, axis=axis) * taps[0]
    for i in range(1, 2 * r + 1):
        out = out + lax.slice_in_dim(xp, i, i + n, axis=axis) * taps[i]
    return out


def antialias(frame: jnp.ndarray, cutoff_nyquist: float = 0.5, *,
              channels_last: bool = False) -> jnp.ndarray:
    """Separable Gaussian AA filter on (..., H, W), or on each channel of
    (..., H, W, C) with ``channels_last`` (reflect padding).

    Elementwise float32 products and sums with the taps as static
    constants: no contraction, no transpose, no channel split."""
    taps = aa_taps(cutoff_nyquist)
    w_axis = frame.ndim - (2 if channels_last else 1)
    out = _blur_axis(frame, taps, w_axis)            # along W
    return _blur_axis(out, taps, w_axis - 1)         # along H


def downsample2(frame: jnp.ndarray) -> jnp.ndarray:
    """½-resolution sensor option (paper: 1920x1080 RGB -> 960x540 Bayer)."""
    return frame[..., ::2, ::2]
