"""What every system under test shares: the PRNG key of a seed, the mesh
a cell on several chips shards its slots over, and the keys of a
configuration that describe it and change nothing that runs.

Everything that belongs to one backend (the keys it implements, its
weights, its engine, its operation counts, its comparison) sits in
``systems/<reference>.py``, found by the ``reference`` name its
configuration gives (``spec.system``).
"""

from __future__ import annotations

import jax
import numpy as np

# keys that describe a configuration and change nothing that runs; a
# system refuses every other key it does not implement
CONFIG_NOTES = {"name", "source", "sets", "assumed", "reduced", "reference"}


def seed_key(seed: int):
    """A PRNG key from a seed of any size up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def slot_mesh(chips: int, capacity: int):
    """None on one chip; on more, a ``"data"`` mesh over the first
    ``chips`` devices that the engine shards its slot axis over. A
    capacity the chips do not divide is refused: the engine would
    replicate the slots instead of sharding them."""
    if chips == 1:
        return None
    if capacity % chips:
        raise SystemExit(f"chipbench: {capacity} cameras do not shard "
                         f"over {chips} chips")
    return jax.sharding.Mesh(
        np.asarray(jax.devices()[:chips]), ("data",),
        axis_types=(jax.sharding.AxisType.Auto,))
