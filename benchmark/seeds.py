"""Everything a run draws comes from ``--seed`` through these two helpers.

A seed is any whole number (the driver's exceed 32 bits); both helpers
hash it through numpy's ``SeedSequence`` first.
"""

from __future__ import annotations

import numpy as np


def host_rng(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named stream of a run's seed."""
    words = [ord(ch) for ch in stream]
    return np.random.default_rng([int(seed) % (1 << 64)] + words)


def device_key(seed: int, stream: str = "device"):
    """A JAX key for one named stream of a run's seed."""
    import jax
    a, b = host_rng(seed, stream).integers(0, 1 << 31, size=2)
    return jax.random.fold_in(jax.random.key(int(a)), int(b))
