"""Keys and streams derived from a run's ``--seed``.

The seed may be any whole number (the driver's exceed 32 bits): it is
taken modulo 2**64 and split into the two words of a threefry key.  Every
random thing a run makes (weights, token batches, request mixes, step
keys) comes from this root through a fixed tag, so the same seed gives
the same inputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

WEIGHTS, STEP_KEYS = 1, 3
#: the layer index under which a model's non-layer weights are made
GLOBAL_LAYER = 1_000_000


def root_key(seed: int):
    s = int(seed) % (1 << 64)
    return jnp.asarray(np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


def layer_key(root, layer):
    return jax.random.fold_in(jax.random.fold_in(root, WEIGHTS), layer)


def host_rng(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named stream of the run."""
    words = [int(seed) % (1 << 64) >> 32, int(seed) % (1 << 32)]
    words += [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words))
