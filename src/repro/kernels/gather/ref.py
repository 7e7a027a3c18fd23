"""Pure-jnp oracle for the cache gather."""
from __future__ import annotations

import jax.numpy as jnp


def cache_gather_ref(slots, cache):
    rows = cache[jnp.maximum(slots, 0)]
    return jnp.where((slots >= 0)[:, None], rows, 0.0)
