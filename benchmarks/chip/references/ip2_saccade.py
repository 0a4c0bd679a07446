"""Plain reference of the IP2 saccade serving path, one stream at a time.

Written from the configuration file alone; it imports nothing of the
program under test. Per served frame of one camera stream:

  RGB frame -> separable Gaussian anti-aliasing (-3 dB at
  ``aa_cutoff_nyquist``, reflect padding) -> RGGB mosaic -> non-
  overlapping ``patch``x``patch`` tiles -> patch AC energy -> gaze (the
  k patches the server chose) -> temporal gate: a selected patch is
  re-projected when it was never converted, when its energy moved by at
  least ``delta_threshold`` since its last conversion, or when its held
  charge has aged past the droop budget; every other selected patch
  serves its held ADC code scaled by ``droop ** age`` -> PWM/DAC
  quantized analog projection, charge share /N^2, droop, clip to the
  rail, edge ADC -> dequantize -> embed with the int8-grid embed weights
  -> dense pre-norm transformer (RMSNorm, softmax attention over the k
  tokens, tanh-GELU MLP) -> masked mean pool -> class logits, and the
  last layer's attention received per token -> next-frame saccade scores
  (unobserved patches score the mean observed attention, plus an
  energy-weighted explore term).

Every contraction goes through :func:`mm`, so the whole reference can be
computed at the configured float32 precision or, for the control, with
each matmul done as three bfloat16 passes (hi*hi + hi*lo + lo*hi, what a
TPU's "high" precision does), the next precision below it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm(subscripts: str, a, b, precision: str):
    """One contraction at ``precision``: "float32" (exact float32
    products, float32 sums) or "bf16x3" (the three-pass bfloat16 split)."""
    if precision == "float32":
        return jnp.einsum(subscripts, a, b, precision=HIGHEST)
    if precision != "bf16x3":
        raise ValueError(f"unknown reference precision {precision!r}")

    def split(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
        return hi, lo

    a_hi, a_lo = split(a.astype(jnp.float32))
    b_hi, b_lo = split(b.astype(jnp.float32))
    e = lambda x, y: jnp.einsum(subscripts, x, y, precision=HIGHEST)
    return e(a_hi, b_hi) + (e(a_hi, b_lo) + e(a_lo, b_hi))


# ---------------------------------------------------------------- sizes
def sizes(conf: dict) -> dict:
    """Derived sizes and constants of a configuration."""
    p = conf["patch"]
    gh, gw = conf["frame_h"] // p, conf["frame_w"] // p
    n_patches = gh * gw
    k = max(1, int(round(n_patches * conf["active_fraction"])))
    levels = 2 ** conf["adc_bits"]
    lsb = (conf["adc_v_max"] - conf["adc_v_min"]) / (levels - 1)
    a0 = conf["opamp_dc_gain"]
    droop = a0 / (1.0 + a0)
    # largest hold whose worst-case droop stays within the LSB budget
    code_fs = max(abs(conf["adc_v_min"]), abs(conf["adc_v_max"])) / lsb
    tol = conf["droop_lsb_budget"] / code_fs
    max_hold = int(math.floor(math.log(1.0 - tol) / math.log(droop)))
    return dict(gh=gh, gw=gw, n_patches=n_patches, k=k, n2=p * p,
                levels=levels, lsb=lsb, droop=droop, max_hold=max_hold)


def gaussian_taps(cutoff_nyquist: float) -> jnp.ndarray:
    fc = cutoff_nyquist * 0.5
    sigma = math.sqrt(math.log(2.0) / 2.0) / (2.0 * math.pi * fc)
    r = max(1, int(math.ceil(3.0 * sigma)))
    x = jnp.arange(-r, r + 1, dtype=jnp.float32)
    t = jnp.exp(-0.5 * (x / sigma) ** 2)
    return t / jnp.sum(t)


# ---------------------------------------------------------------- sensor
def sensor(conf: dict, rgb, a_rgb, precision: str):
    """rgb (B, H, W, 3) -> (patches (B, P, N2), struck weights (M, N2))."""
    taps = gaussian_taps(conf["aa_cutoff_nyquist"])
    r = (taps.shape[0] - 1) // 2

    def blur_last(x):
        xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(r, r)], mode="reflect")
        n = x.shape[-1]
        if precision == "float32":
            # exact float32 products and sums, without an MXU pass per tap
            out = xp[..., 0:n] * taps[0]
            for i in range(1, 2 * r + 1):
                out = out + xp[..., i:i + n] * taps[i]
            return out
        win = jnp.stack([xp[..., i:i + n] for i in range(2 * r + 1)], axis=-1)
        return mm("...t,t->...", win, taps, precision)

    chans = []
    for c in range(3):
        x = blur_last(rgb[..., c])                               # along W
        x = jnp.swapaxes(blur_last(jnp.swapaxes(x, -1, -2)), -1, -2)
        chans.append(x)
    img = jnp.stack(chans, axis=-1)
    h, w = img.shape[-3], img.shape[-2]
    # RGGB: R at (even, even), B at (odd, odd), G elsewhere
    rows = jnp.arange(h)[:, None] % 2
    cols = jnp.arange(w)[None, :] % 2
    site = jnp.where((rows == 0) & (cols == 0), 0,
                     jnp.where((rows == 1) & (cols == 1), 2, 1))
    if precision == "float32":
        raw = jnp.take_along_axis(img, site[None, :, :, None], axis=-1)[..., 0]
    else:
        onehot = jax.nn.one_hot(site, 3, dtype=jnp.float32)
        raw = mm("bhwc,hwc->bhw", img, onehot, precision)
    p = conf["patch"]
    b = raw.shape[0]
    tiles = raw.reshape(b, h // p, p, w // p, p).transpose(0, 1, 3, 2, 4)
    patches = tiles.reshape(b, (h // p) * (w // p), p * p)
    psite = site[:p, :p].reshape(-1)
    m = a_rgb.shape[0]
    struck = jnp.take_along_axis(
        a_rgb.reshape(m, p * p, 3), psite[None, :, None], axis=-1)[..., 0]
    return patches, struck


def patch_energy(patches):
    c = patches - jnp.mean(patches, axis=-1, keepdims=True)
    return jnp.mean(c * c, axis=-1)


def adc_codes(conf: dict, sz: dict, patches, struck, precision: str):
    """Selected patches (B, j, N2) -> int8 ADC codes (B, j, M)."""
    n_pwm = 2 ** conf["pwm_bits"] - 1
    p_q = jnp.round(jnp.clip(patches, 0.0, 1.0) * n_pwm) / n_pwm
    lv = 2 ** (conf["weight_bits"] - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(struck), axis=-1, keepdims=True),
                        1e-12) / lv
    w_q = jnp.clip(jnp.round(struck / scale), -lv, lv) * scale
    acc = mm("bjn,mn->bjm", p_q, w_q, precision) / sz["n2"]
    v = conf["v_ref"] + sz["droop"] * acc
    v = jnp.clip(v, -conf["analog_clip_v"], conf["analog_clip_v"])
    v = jnp.clip(v, conf["adc_v_min"], conf["adc_v_max"])
    code = jnp.round((v - conf["adc_v_min"]) / sz["lsb"]) - sz["levels"] // 2
    return code.astype(jnp.int8)


# ---------------------------------------------------------------- backend
def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def encoder(conf: dict, w: dict, x, precision: str):
    """x (B, k, d) -> (logits (B, C), attention received per token (B, k))."""
    h_n, d = conf["n_heads"], conf["d_model"]
    dh = d // h_n
    n_layers = len(w["layers"])
    received = None
    for li, lp in enumerate(w["layers"]):
        a = lp["attn"]
        h = rms(x, lp["norm1"], conf["norm_eps"])
        q = mm("bsd,dhk->bshk", h, a["wq"], precision) + a["bq"]
        kk = mm("bsd,dhk->bshk", h, a["wk"], precision) + a["bk"]
        v = mm("bsd,dhk->bshk", h, a["wv"], precision) + a["bv"]
        s = mm("bqhk,bshk->bhqs", q, kk, precision) / jnp.sqrt(jnp.float32(dh))
        probs = jax.nn.softmax(s, axis=-1)
        o = mm("bhqs,bshk->bqhk", probs, v, precision)
        x = x + mm("bshk,hkd->bsd", o, a["wo"], precision)
        h = rms(x, lp["norm2"], conf["norm_eps"])
        u = mm("bsd,df->bsf", h, lp["mlp"]["w_up"], precision) + lp["mlp"]["b_up"]
        u = jax.nn.gelu(u, approximate=True)
        x = x + mm("bsf,fd->bsd", u, lp["mlp"]["w_down"], precision) \
            + lp["mlp"]["b_down"]
        if li == n_layers - 1:
            # every token is a query: mean over heads and queries
            received = jnp.sum(probs, axis=(1, 2)) / (probs.shape[1]
                                                      * probs.shape[2])
    x = rms(x, w["final_norm"], conf["norm_eps"])
    pooled = jnp.mean(x, axis=1)
    return mm("bd,dc->bc", pooled, w["head"], precision), received


def saccade_scores(conf: dict, sz: dict, received, gaze, energy):
    """Next-frame selection scores (B, P)."""
    b = jnp.arange(gaze.shape[0])[:, None]
    att = jnp.zeros((gaze.shape[0], sz["n_patches"]), jnp.float32)
    att = att.at[b, gaze].max(received)
    observed = jnp.zeros(att.shape, bool).at[b, gaze].set(True)
    n_obs = jnp.maximum(jnp.sum(observed, axis=-1, keepdims=True), 1)
    base = jnp.sum(att, axis=-1, keepdims=True) / n_obs
    scores = jnp.where(observed, att, base)
    e = energy / jnp.maximum(jnp.max(energy, axis=-1, keepdims=True), 1e-9)
    return scores + max(conf["explore"], 1e-3) * base * e


def topk(scores, k):
    scores = jnp.where(scores == 0, 0.0, scores)
    return jax.lax.top_k(scores, k)[1].astype(jnp.int32)


def gaze_gap(scores, gaze, k):
    """How far the served gaze lies below the reference's own choice:
    the largest (k-th best score - score of a served patch), over the
    largest score; 0 when the served gaze is a top-k set."""
    kth = jax.lax.top_k(scores, k)[0][:, -1:]
    got = jnp.take_along_axis(scores, gaze, axis=-1)
    top = jnp.maximum(jnp.max(jnp.abs(scores), axis=-1), 1e-30)
    return jnp.maximum(jnp.max(kth - got, axis=-1), 0.0) / top


# ---------------------------------------------------------------- replay
def init_state(conf: dict, batch: int) -> dict:
    sz = sizes(conf)
    p, m = sz["n_patches"], conf["n_vectors"]
    return dict(codes=jnp.zeros((batch, p, m), jnp.int8),
                e_ref=jnp.zeros((batch, p), jnp.float32),
                age=jnp.zeros((batch, p), jnp.int32),
                valid=jnp.zeros((batch, p), bool),
                scores=jnp.zeros((batch, p), jnp.float32),
                served=jnp.zeros((batch,), jnp.int32))


def frames_of(scenes, scene_idx, box, color):
    """Frames (B, H, W, 3): scene ``scene_idx`` with a ``color`` square of
    side ``box[2]`` at ``(box[0], box[1])`` (side 0: no square)."""
    img = scenes[scene_idx]
    h, w = img.shape[1], img.shape[2]
    yy = jnp.arange(h)[None, :, None]
    xx = jnp.arange(w)[None, None, :]
    y0, x0, s = (box[:, i][:, None, None] for i in range(3))
    inside = (yy >= y0) & (yy < y0 + s) & (xx >= x0) & (xx < x0 + s)
    return jnp.where(inside[..., None], color[:, None, None, :], img)


def frame_step(conf: dict, w: dict, st: dict, rgb, gaze, fed, precision):
    """One served frame for each of B streams (rows with ``fed`` False
    hold). Returns (state, logits (B, C), gaze gap (B,))."""
    sz = sizes(conf)
    k = sz["k"]
    patches, struck = sensor(conf, rgb, w["a_rgb"], precision)
    energy = patch_energy(patches)
    first = st["served"] == 0
    want = jnp.where(first[:, None], energy, st["scores"])
    gap = gaze_gap(want, gaze, k)

    take = lambda a: jnp.take_along_axis(a, gaze, axis=-1)
    e_now = take(energy)
    stale = (~take(st["valid"]) | (jnp.abs(e_now - take(st["e_ref"]))
                                   >= conf["delta_threshold"])
             | (take(st["age"]) >= sz["max_hold"]))
    sel = jnp.take_along_axis(patches, gaze[..., None], axis=-2)
    new = adc_codes(conf, sz, sel, struck, precision)
    b = jnp.arange(gaze.shape[0])[:, None]
    held = st["codes"][b, gaze]
    codes = st["codes"].at[b, gaze].set(jnp.where(stale[..., None], new, held))
    age = jnp.where(st["valid"], st["age"] + 1, st["age"])
    age = age.at[b, gaze].set(jnp.where(stale, 0, take(age)))
    e_ref = st["e_ref"].at[b, gaze].set(jnp.where(stale, e_now,
                                                  take(st["e_ref"])))
    valid = st["valid"].at[b, gaze].set(True)

    served_age = take(age).astype(jnp.float32)
    gain = jnp.power(jnp.float32(sz["droop"]), served_age)
    zero = (conf["adc_v_min"] + (sz["levels"] // 2) * sz["lsb"]
            - conf["v_ref"]) + w["bias"]
    feats = (codes[b, gaze].astype(jnp.float32) * sz["lsb"] + zero) \
        * gain[..., None]
    x = mm("bkm,md->bkd", feats, w["embed"], precision) + w["pos"][gaze]
    logits, received = encoder(conf, w, x, precision)
    scores = saccade_scores(conf, sz, received, gaze, energy)

    nxt = dict(codes=codes, e_ref=e_ref, age=age, valid=valid, scores=scores,
               served=st["served"] + 1)
    keep = lambda n, o: jnp.where(
        fed.reshape(fed.shape + (1,) * (n.ndim - 1)), n, o)
    return jax.tree.map(keep, nxt, st), logits, gap


def replay(conf: dict, w: dict, scenes, xs: dict, precision: str):
    """Replay B streams over T steps: ``xs`` holds (T, B, ...) arrays
    ``scene``, ``box``, ``color``, ``gaze`` and ``fed``. Returns logits
    (T, B, C) and gaze gaps (T, B)."""
    batch = xs["fed"].shape[1]

    def body(st, x):
        rgb = frames_of(scenes, x["scene"], x["box"], x["color"])
        st, logits, gap = frame_step(conf, w, st, rgb, x["gaze"], x["fed"],
                                     precision)
        return st, (logits, gap)

    _, (logits, gaps) = jax.lax.scan(body, init_state(conf, batch), xs)
    return logits, gaps
