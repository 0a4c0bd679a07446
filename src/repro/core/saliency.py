"""Salient patch selection / partial observation (paper §1, §2.1).

Only the outputs of a selected set of salient patches (e.g. <25 %) are
converted to the digital domain. The selection comes from the backend
model's saccadic prediction of the previous frame ("shifted attention");
deselected patches drain their photodiodes and power down, so they cost
neither ADC conversions nor bandwidth.

The primary representation is **index-first** (DESIGN.md §3): a static-size
list of exactly-k active patch indices, which drives the gather *before*
the analog projection so compute scales with the active fraction. Boolean
masks remain as a derived view for the dense (training / co-design) path:

* ``topk_patch_indices`` — exactly-k selector with deterministic
  tie-breaking (equal scores -> lowest patch index wins);
* ``topk_patch_mask`` — boolean view of the same selection (always exactly
  k true entries, even with tied scores);
* ``indices_from_mask`` / ``mask_from_indices`` — conversions between the
  two views, static shapes for jit;
* ``gather_patches`` — the select->gather step: pick the active rows of a
  (..., P, N) array ahead of projection;
* ``apply_patch_mask`` — zero deselected patch features (dense path);
* ``compact_active`` — gather of only the active patch features, the
  bandwidth-true representation streamed off-sensor.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def topk_patch_indices(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """Exactly-k most-salient patch indices, deterministically tie-broken.

    ``jax.lax.top_k`` guarantees that among equal scores the lower-index
    element appears first; we lean on that contract so the selection is a
    pure function of the scores (a ``scores >= thresh`` mask is not: every
    patch tied at the threshold gets selected, breaking exactly-k).

    Args:
      scores: (..., n_patches) saliency scores (patch energy or the
        backend's attention rollout).
      k: number of patches to keep (static).

    Returns:
      (..., k) int32 indices, sorted by descending score (ties: ascending
      patch index).
    """
    n = scores.shape[-1]
    if not 1 <= k <= n:
        raise ValueError(f"k={k} out of range for {n} patches")
    # top_k orders -0.0 below +0.0; they are one score, tied by index
    scores = jnp.where(scores == 0, jnp.zeros_like(scores), scores)
    _, idx = jax.lax.top_k(scores, k)
    return idx.astype(jnp.int32)


def mask_from_indices(indices: jnp.ndarray, n_patches: int) -> jnp.ndarray:
    """(..., k) indices -> (..., n_patches) boolean mask."""
    one_hot = jax.nn.one_hot(indices, n_patches, dtype=jnp.bool_)
    return jnp.any(one_hot, axis=-2)


def indices_from_mask(mask: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(..., P) boolean mask -> ((..., k) indices, (..., k) valid).

    Static shape for jit: if fewer than k patches are active the tail
    repeats inactive slots (marked ``valid=False``); if more are active the
    lowest k indices win. Active indices come out in ascending order.
    """
    idx = jnp.argsort(~mask, axis=-1, stable=True)[..., :k].astype(jnp.int32)
    valid = jnp.take_along_axis(mask, idx, axis=-1)
    return idx, valid


def topk_patch_mask(scores: jnp.ndarray, active_fraction: float) -> jnp.ndarray:
    """Boolean mask keeping exactly the top ``active_fraction`` of patches.

    Built on the index-first selector, so tied scores can never over-select
    (a plain ``scores >= thresh`` comparison selects *every* patch at the
    threshold value, breaking the exactly-k contract of the compact path).
    """
    n = scores.shape[-1]
    k = max(1, int(round(n * active_fraction)))
    return mask_from_indices(topk_patch_indices(scores, k), n)


def patch_energy(patches: jnp.ndarray) -> jnp.ndarray:
    """Simple saliency proxy: AC energy of each patch (..., P, N²) -> (..., P)."""
    centered = patches - jnp.mean(patches, axis=-1, keepdims=True)
    return jnp.mean(centered * centered, axis=-1)


def apply_patch_mask(features: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Zero out deselected patches: (..., P, M) * (..., P, 1)."""
    return features * mask[..., None].astype(features.dtype)


def gather_patches(patches: jnp.ndarray, indices: jnp.ndarray) -> jnp.ndarray:
    """Select->gather: (..., P, N) rows at (..., k) indices -> (..., k, N).

    Differentiable (scatter-add transpose), so the STE co-design gradients
    flow through the compact path into the frontend weights.
    """
    return jnp.take_along_axis(patches, indices[..., None], axis=-2)


def compact_active(
    features: jnp.ndarray, mask: jnp.ndarray, k: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Gather exactly-k active patch features (static shape for jit).

    Returns (compact_features (..., k, M), indices (..., k)). If fewer than
    k patches are active the tail repeats inactive patches (masked
    downstream); if more, the lowest-index k win (mask should be top-k).
    """
    idx, _ = indices_from_mask(mask, k)
    return gather_patches(features, idx), idx


def active_fraction(mask: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(mask.astype(jnp.float32), axis=-1)
