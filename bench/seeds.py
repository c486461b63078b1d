"""Keys drawn from a run's ``--seed``.

The weights, the prompts and the sample of requests the check compares each
come from their own stream of the one seed. The program draws its weights
from ``key(seed, "weights")`` and the reference draws its own, by the same
recipe, from the same key: it never reads the program's arrays.
"""

from __future__ import annotations

import jax

STREAMS = {"weights": 1, "prompts": 2}


def key(seed: int, stream: str) -> jax.Array:
    """A PRNG key for ``stream``; every bit of a seed up to 2**64 counts."""
    seed = int(seed)
    base = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(base, STREAMS[stream])
