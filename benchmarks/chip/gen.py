"""What every family's inputs are made from: the run's key from
``--seed``, and a configuration group as a static argument of a jitted
call. A family makes its own state and data (``families/<family>.py``
``make_inputs``).

A seed is any whole number that fits in 64 bits; its two 32-bit halves
both enter the key.
"""
from __future__ import annotations

import jax


def seed_key(seed):
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed must lie in [0, 2**64), not {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def items(d):
    """A dict as a hashable, sorted item tuple (nested dicts too)."""
    return tuple(sorted((k, items(v) if isinstance(v, dict) else v)
                        for k, v in d.items()))
