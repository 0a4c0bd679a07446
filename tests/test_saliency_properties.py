"""Property-based tests for the index-first selection API
(`core/saliency.py`, DESIGN.md §3.1): round-trip between the index and
mask views, the exactly-k contract under arbitrary ties, deterministic
tie-breaking, and the gather's scatter-add transpose.

Each invariant is a plain checker over (scores|mask, k); hypothesis
drives them with adversarial inputs when installed (requirements-dev),
and a seeded deterministic battery — heavy on ties, the known failure
mode of threshold-style selection — always runs so the invariants stay
covered even without hypothesis (e.g. a bare-jax container).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.saliency import (
    gather_patches,
    indices_from_mask,
    mask_from_indices,
    topk_patch_indices,
    topk_patch_mask,
)

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# invariant checkers (shared by the hypothesis and deterministic drivers)
# ---------------------------------------------------------------------------

def check_exactly_k_and_tiebreak(scores: np.ndarray, k: int) -> None:
    """topk_patch_indices returns k DISTINCT indices equal to the first k
    of a stable sort by descending score (ties -> ascending index), and
    the mask view has exactly k True entries."""
    n = scores.shape[-1]
    idx = np.asarray(topk_patch_indices(jnp.asarray(scores), k))
    assert idx.shape == (k,) and len(set(idx.tolist())) == k
    # XLA compares float32 subnormals as zero, and -0.0 ties +0.0
    flushed = np.where(np.abs(scores) < np.finfo(np.float32).tiny,
                       np.float32(0.0), scores)
    oracle = np.argsort(-flushed, kind="stable")[:k]
    np.testing.assert_array_equal(idx, oracle)
    mask = np.asarray(mask_from_indices(jnp.asarray(idx), n))
    assert int(mask.sum()) == k
    frac_mask = np.asarray(topk_patch_mask(jnp.asarray(scores), k / n))
    np.testing.assert_array_equal(mask, frac_mask)


def check_indices_mask_roundtrip(scores: np.ndarray, k: int) -> None:
    """indices -> mask -> indices recovers the same selection (as a set;
    index view is score-ordered, mask view is ascending) with all-valid."""
    n = scores.shape[-1]
    idx = topk_patch_indices(jnp.asarray(scores), k)
    mask = mask_from_indices(idx, n)
    idx2, valid2 = indices_from_mask(mask, k)
    assert bool(valid2.all())
    assert set(np.asarray(idx).tolist()) == set(np.asarray(idx2).tolist())


def check_mask_indices_roundtrip(mask: np.ndarray, k: int) -> None:
    """mask -> indices -> mask: exact reconstruction when <= k active
    (fillers are flagged invalid), lowest-k active indices when over."""
    c = int(mask.sum())
    idx, valid = indices_from_mask(jnp.asarray(mask), k)
    assert int(valid.sum()) == min(c, k)
    back = np.zeros_like(mask)
    sel = np.asarray(idx)[np.asarray(valid)]
    back[sel] = True
    if c <= k:
        np.testing.assert_array_equal(back, mask)
    else:
        want = np.zeros_like(mask)
        want[np.flatnonzero(mask)[:k]] = True
        np.testing.assert_array_equal(back, want)


def check_gather_grad_is_scatter_add(
    patches: np.ndarray, indices: np.ndarray, cotangent: np.ndarray
) -> None:
    """d/dx sum(gather(x, idx) * g) == scatter-add of g at idx — duplicate
    indices must ACCUMULATE (the STE co-design gradient contract)."""
    x = jnp.asarray(patches)
    idx = jnp.asarray(indices, jnp.int32)
    g = jnp.asarray(cotangent)
    grad = jax.grad(lambda p: jnp.sum(gather_patches(p, idx) * g))(x)
    want = np.zeros_like(patches)
    np.add.at(want, np.asarray(indices), np.asarray(cotangent))
    np.testing.assert_allclose(np.asarray(grad), want, atol=1e-6)


# ---------------------------------------------------------------------------
# hypothesis drivers (adversarial inputs; skipped without hypothesis)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    # scores drawn from a tiny value set => dense ties by construction
    tied_scores = st.integers(2, 24).flatmap(
        lambda n: st.lists(
            st.sampled_from([0.0, -1.0, 1.0, 0.5, 3.25]), min_size=n, max_size=n
        ).map(lambda v: np.asarray(v, np.float32))
    )
    float_scores = st.integers(2, 24).flatmap(
        lambda n: st.lists(
            st.floats(-100, 100, allow_nan=False, width=32),
            min_size=n, max_size=n,
        ).map(lambda v: np.asarray(v, np.float32))
    )

    class TestHypothesis:
        @settings(max_examples=60, deadline=None)
        @given(st.data(), st.one_of(tied_scores, float_scores))
        def test_exactly_k_and_tiebreak(self, data, scores):
            k = data.draw(st.integers(1, scores.shape[-1]))
            check_exactly_k_and_tiebreak(scores, k)

        @settings(max_examples=40, deadline=None)
        @given(st.data(), st.one_of(tied_scores, float_scores))
        def test_indices_mask_roundtrip(self, data, scores):
            k = data.draw(st.integers(1, scores.shape[-1]))
            check_indices_mask_roundtrip(scores, k)

        @settings(max_examples=40, deadline=None)
        @given(st.data(), st.integers(2, 24))
        def test_mask_indices_roundtrip(self, data, n):
            mask = np.asarray(
                data.draw(st.lists(st.booleans(), min_size=n, max_size=n)))
            k = data.draw(st.integers(1, n))
            check_mask_indices_roundtrip(mask, k)

        @settings(max_examples=30, deadline=None)
        @given(st.data(), st.integers(2, 8), st.integers(1, 6), st.integers(1, 4))
        def test_gather_grad_is_scatter_add(self, data, p, k, nfeat):
            rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
            idx = np.asarray(
                data.draw(st.lists(st.integers(0, p - 1), min_size=k, max_size=k)))
            check_gather_grad_is_scatter_add(
                rng.normal(size=(p, nfeat)).astype(np.float32), idx,
                rng.normal(size=(k, nfeat)).astype(np.float32))


# ---------------------------------------------------------------------------
# deterministic battery (always runs; tie-heavy by construction)
# ---------------------------------------------------------------------------

def _score_battery():
    cases = [
        np.zeros(7, np.float32),                       # all tied
        np.ones(16, np.float32) * -2.5,                # all tied, negative
        np.asarray([1, 0, 1, 0, 1, 0, 1, 0], np.float32),   # two-value comb
        np.asarray([3, 3, 3, 1, 1, 1, 2, 2], np.float32),   # tied plateaus
        np.asarray([0.5] * 5 + [1.0], np.float32),     # unique max, tied rest
        np.asarray([-0.0, 0.0, -0.0], np.float32),     # signed zeros tie
        np.asarray([0.0, 1.2e-39, -1e-40], np.float32),  # subnormals tie 0
    ]
    rng = np.random.default_rng(1234)
    for n in (2, 5, 13, 24):
        cases.append(rng.choice([0.0, 1.0, -1.0], size=n).astype(np.float32))
        cases.append(rng.normal(size=n).astype(np.float32))
    return cases


@pytest.mark.parametrize("scores", _score_battery(), ids=lambda s: f"n{len(s)}")
def test_exactly_k_and_tiebreak_battery(scores):
    for k in {1, len(scores) // 2, len(scores)} - {0}:
        check_exactly_k_and_tiebreak(scores, k)
        check_indices_mask_roundtrip(scores, k)


@pytest.mark.parametrize("seed", range(8))
def test_mask_roundtrip_battery(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 24))
    mask = rng.random(n) < rng.random()    # varying densities incl. 0 and 1
    for k in {1, max(1, n // 2), n}:
        check_mask_indices_roundtrip(mask, k)


@pytest.mark.parametrize("seed", range(6))
def test_gather_grad_battery(seed):
    rng = np.random.default_rng(100 + seed)
    p, nfeat = int(rng.integers(2, 9)), int(rng.integers(1, 5))
    k = int(rng.integers(1, 7))
    idx = rng.integers(0, p, size=k)       # duplicates likely: accumulation
    check_gather_grad_is_scatter_add(
        rng.normal(size=(p, nfeat)).astype(np.float32), idx,
        rng.normal(size=(k, nfeat)).astype(np.float32))
