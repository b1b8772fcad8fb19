"""What every model kind (``bench/models/<kind>.py``) shares: the PRNG key
its random weights are drawn from, made from ``--seed``."""
from __future__ import annotations

import numpy as np


def jax_key(seed: int):
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    import jax
    hi, lo = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(hi)), int(lo))
