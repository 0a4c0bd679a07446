"""Pallas TPU kernel — ragged stale-Q compact attention (DESIGN.md §14).

The delta-gated backend (``models/backend_delta.py``) re-attends only the
``j`` stale query rows of each slot against the FULL cached key/value set
(one changed key perturbs every query, but an unchanged query row only
needs recomputing when its own input changed — at eps > 0 the held rows
keep their cached outputs). Stale rows are ranked stale-first by the
temporal frontend, so per-slot stale counts are a PREFIX length — the
same scalar-prefetched ragged banking scheme as the §11 megakernel
transfers directly:

* grid = (slots, heads, query banks); a query bank is active iff its
  first row position is below its slot's count (``pl.when`` — inactive
  banks skip the MXU entirely);
* the query index_map clamps inactive banks onto the slot's last active
  bank and inactive K/V/mask blocks pin to slot 0, so consecutive
  inactive steps present unchanged block indices and the pipeliner
  elides their DMA copies — held rows cost zero FLOPs and zero VMEM
  traffic, not masked-but-computed work;
* counts are DATA: one compile serves every stale pattern the gate can
  produce, including count 0 (a fully-held slot streams nothing).

The body mirrors ``vit._encoder_attention``'s arithmetic exactly — same
contraction order, divide-by-sqrt(dh) (not multiply-by-reciprocal), mask
via ``where(mask, scores, NEG_INF)`` — so the kernel's rows match the
dense einsum path on the stale prefix (asserted in
tests/test_backend_delta.py). Rows at positions >= their slot's count
are zero, never garbage.

Block shapes come from :func:`pick_block_q`, which minimizes the
roofline cost model's attention terms
(:func:`repro.roofline.analysis.delta_attention_cost`) over candidate
bank heights at the expected stale prefix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # matches models/vit.py — the masking constant is part
                 # of the parity contract


def _q_map(block_q):
    """Query index_map: clamp inactive banks onto the slot's last active
    bank so their DMA copies are elided (§11 idiom)."""

    def m(b, h, qb, cnt):
        n_act = (cnt[b] + block_q - 1) // block_q
        return (b, h, jnp.minimum(qb, jnp.maximum(n_act - 1, 0)), 0)

    return m


def _kv_map(block_q):
    """K/V index_map: a fully-inactive step pins the block to slot 0 so
    held slots stream no key/value bytes at all."""

    def m(b, h, qb, cnt):
        act = (qb * block_q) < cnt[b]
        return (jnp.where(act, b, 0), jnp.where(act, h, 0), 0, 0)

    return m


def _mask_map(block_q):
    def m(b, h, qb, cnt):
        act = (qb * block_q) < cnt[b]
        return (jnp.where(act, b, 0), 0, 0)

    return m


def _delta_attn_kernel(
    cnt_ref, q_ref, k_ref, v_ref, m_ref, o_ref, *, block_q: int, dh: int
):
    """One (slot, head, query bank) step: scores over the full key set,
    masked softmax, value mix — the exact dense arithmetic on the bank's
    rows. ``dh`` is the REAL head dim (the refs may be lane-padded; the
    pad columns are zero so the contractions are value-preserving, but
    the softmax scale must use the true dimension)."""
    b, qb = pl.program_id(0), pl.program_id(2)
    cnt = cnt_ref[b]
    act = (qb * block_q) < cnt

    @pl.when(act)
    def _compute():
        qq = q_ref[0, 0]   # (block_q, dh_p)
        kk = k_ref[0, 0]   # (S_p, dh_p)
        vv = v_ref[0, 0]
        sc = jax.lax.dot_general(
            qq, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        sc = sc / jnp.sqrt(jnp.asarray(dh, sc.dtype))
        msk = m_ref[0] > 0.5   # (S_p,)
        sc = jnp.where(msk[None, :], sc, NEG_INF)
        probs = jax.nn.softmax(sc, axis=-1)
        o = jax.lax.dot_general(
            probs.astype(vv.dtype), vv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # the bank may straddle the count: rows past it are zero, never
        # garbage (the gate masks on them)
        row = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, o.shape, 0)
        o_ref[0, 0] = jnp.where(row < cnt, o, 0.0).astype(o_ref.dtype)

    @pl.when(~act)
    def _zero():
        o_ref[0, 0] = jnp.zeros_like(o_ref[0, 0])


def _pad_axis(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit, static_argnames=("block_q", "lane", "interpret")
)
def delta_attention_pallas(
    q: jnp.ndarray,         # (B, S, H, dh) stale-prefix query rows
    k: jnp.ndarray,         # (B, S, H, dh) full key set
    v: jnp.ndarray,         # (B, S, H, dh)
    key_mask: jnp.ndarray,  # (B, S) bool — valid key tokens
    q_counts: jnp.ndarray,  # (B,) int32 stale prefix length (DATA)
    block_q: int = 8,
    lane: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns (B, S, H, dh): row r of slot b holds the attention output
    of query r when ``r < q_counts[b]``, else zeros."""
    B, S, H, dh = q.shape
    assert k.shape == q.shape and v.shape == q.shape
    assert key_mask.shape == (B, S)

    def prep(x):  # (B,S,H,dh) -> lane-padded (B,H,S_p,dh_p)
        x = jnp.transpose(x, (0, 2, 1, 3))
        return _pad_axis(_pad_axis(x, 3, lane), 2, block_q)

    qt, kt, vt = prep(q), prep(k), prep(v)
    s_p, dh_p = qt.shape[2], qt.shape[3]
    # padded key rows are invalid: they mask to NEG_INF and mix nothing
    # (B, 1, S_p): a (1, S_p) block per slot spans the array's last two dims
    mask_f = _pad_axis(key_mask.astype(jnp.float32), 1, block_q)[:, None, :]

    grid = (B, H, s_p // block_q)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh_p), _q_map(block_q)),
            pl.BlockSpec((1, 1, s_p, dh_p), _kv_map(block_q)),
            pl.BlockSpec((1, 1, s_p, dh_p), _kv_map(block_q)),
            pl.BlockSpec((None, 1, s_p), _mask_map(block_q)),
        ],
        # output map is NOT clamped: every bank owns its own block
        out_specs=pl.BlockSpec(
            (1, 1, block_q, dh_p), lambda b, h, qb, cnt: (b, h, qb, 0)
        ),
    )
    out = pl.pallas_call(
        functools.partial(_delta_attn_kernel, block_q=block_q, dh=dh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, s_p, dh_p), q.dtype),
        interpret=interpret,
    )(q_counts.astype(jnp.int32), qt, kt, vt, mask_f)
    return jnp.transpose(out[:, :, :S, :dh], (0, 2, 1, 3))


def pick_block_q(
    k_tokens: int, d_model: int, n_heads: int,
    expect_stale: int | None = None,
    candidates: tuple = (4, 8, 16, 32),
) -> int:
    """Roofline-picked query bank height: minimize the modeled cost of
    the kernel grid at the expected stale prefix (default half the
    tokens — the gate's break-even regime). Larger banks amortize K/V
    streaming but round the prefix up harder; the §11 cost model arbitrates."""
    from repro.roofline import analysis  # lazy: keep kernels import-light

    j = min(expect_stale if expect_stale is not None else k_tokens // 2,
            k_tokens) or 1
    best, best_cost = candidates[0], None
    for bq in candidates:
        if bq > max(k_tokens, 1):
            break
        c = analysis.delta_attention_cost(
            j, k_tokens, d_model, n_heads, block_q=bq)
        t = c["time_s"]
        if best_cost is None or t < best_cost:
            best, best_cost = bq, t
    return best
