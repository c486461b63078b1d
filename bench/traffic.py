"""The general generator: a traffic mix file turned into requests.

A mix (``bench/traffic/<name>.json``) states:

    loop           "closed": the next batch is dispatched when the previous
                   one has finished decoding; each request is due at its
                   batch's dispatch
    batch          requests per batch
    prompt_tokens  tokens per prompt
    new_tokens     decode steps per request (each request is served
                   1 + new_tokens tokens: the first from prefill)
    transfer       "compressed" or "raw": how the cache crosses to decode
    resident       "raw" or "compressed": how the decode side holds it
    trace_batches  batches under the profiler in a ``--trace 1`` run
    check_requests requests the check compares with the reference

Prompts are token ids drawn on the device from the run's seed, one key per
batch, so the same seed gives the same batches in the same order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import seeds

LOOPS = ("closed",)


class ClosedLoop:
    def __init__(self, mix: dict, vocab: int, seed: int):
        if mix["loop"] not in LOOPS:
            raise ValueError(f"loop {mix['loop']!r}: known {LOOPS}")
        self.batch = int(mix["batch"])
        self.prompt_tokens = int(mix["prompt_tokens"])
        self.new_tokens = int(mix["new_tokens"])
        self._key = seeds.key(seed, "prompts")
        shape = (self.batch, self.prompt_tokens)
        self._draw = jax.jit(lambda k, i: jax.random.randint(
            jax.random.fold_in(k, i), shape, 0, vocab, jnp.int32))

    def prompts(self, index: int) -> dict:
        """The prompt batch of dispatch ``index`` (0 is the warm-up)."""
        return {"tokens": self._draw(self._key, index)}

    def prompt_rows(self, index: int) -> np.ndarray:
        return np.asarray(self.prompts(index)["tokens"])
