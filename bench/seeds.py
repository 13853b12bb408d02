"""Seeds: any whole number (the driver's exceed 32 bits) maps to one
numpy generator and one JAX key, the same for the same seed."""

from __future__ import annotations

import numpy as np

_MASK64 = (1 << 64) - 1


def numpy_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) & _MASK64)


def jax_key(seed: int):
    """A JAX key from all 64 low bits of ``seed``: the low word seeds
    it and the high word is folded in."""
    import jax
    s = int(seed) & _MASK64
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    return jax.random.fold_in(key, s >> 32)
