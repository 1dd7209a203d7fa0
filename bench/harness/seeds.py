"""Keys and generators from `--seed`, which may exceed 32 bits."""
from __future__ import annotations

import numpy as np


def jax_key(seed: int, stream: int = 0):
    """A JAX key that depends on all 64 bits of `seed` (PRNGKey alone
    keeps only the low 32 bits when x64 is off)."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def host_rng(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, stream])
