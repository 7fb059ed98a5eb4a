"""Seeded weights, made on the device in one jitted call, in the type
they are served in.

The benchmark owns the weights: the program under test and the plain
reference both read the same arrays, so neither makes what the other is
compared with.  Which arrays there are, and how widely each is drawn,
is the configuration's architecture kind's (``arch/<kind>.py``:
``shapes``, ``std``); its ``to_program`` arranges them in the program's
parameter tree.  The draw from the seed is the same for every kind.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def make(kind, config: Dict[str, Any], vocab_rows: int, seed: int, dtype):
    """All of ``kind``'s weights for ``config`` from ``seed``, on the
    default device, in ``dtype``."""
    spec = kind.shapes(config, vocab_rows)
    names = sorted(spec)

    def build(key):
        keys = jax.random.split(key, len(names))
        return {n: (jax.random.truncated_normal(k, -2.0, 2.0, spec[n],
                                                jnp.float32)
                    * kind.std(n, spec[n])).astype(dtype)
                for n, k in zip(names, keys)}

    key = jax.random.fold_in(jax.random.PRNGKey(0), seed % (2 ** 31))
    key = jax.random.fold_in(key, seed // (2 ** 31))
    return jax.block_until_ready(jax.jit(build)(key))
