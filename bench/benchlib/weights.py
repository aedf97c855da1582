"""Weights drawn from the seed, on the device in one jitted call, in the
type they are served in and in the layout the serving engine takes. What
they are is the configuration's architecture module's (bench/archs/<arch>.py
``draw``); this module gives the seed's key and the pieces every
architecture draws with.

The benchmark makes these; the program under test and the plain reference
both read them.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import spec

VOCAB_PAD = 256  # the engine pads the vocabulary to a multiple of this


def seed_key(seed: int, salt: int = 0):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(salt)
    while True:
        key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
        seed >>= 32
        if not seed:
            return key


def normal(key, shape, std, dtype):
    """N(0, std^2) drawn in float32, then cast to ``dtype``."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def gain(key, shape):
    """A norm's gain: 1 + 0.05 N(0, 1), float32."""
    return 1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)


def generate(config: Dict[str, Any], seed: int):
    """(params, sparse params or None) for a configuration file, on the
    default device, from ``seed``."""
    return spec.arch(config).draw(seed_key(seed, salt=1), config)
