"""Prefill worker: runs the prompt, produces the cache the PD boundary ships.

In the disaggregated deployment this code runs on the prefill pod; the jitted
``prefill_step`` is the unit of work per prompt batch, and its output cache is
handed to the transfer session (serving/session.py) — compressed with
SplitZip — before any decode work can start (the paper's critical path).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import model as M
from repro.models.kvcache import DecodeState


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PrefillOutput:
    """What the prefill worker emits per batch."""
    first_token: jax.Array        # (B,) greedy first generated token
    last_logits: jax.Array        # (B, V)
    state: DecodeState            # the cache to transfer


def prefill_step(params, batch: Dict, cfg: ArchConfig, *,
                 max_seq: Optional[int] = None, kv_block: int = 1024
                 ) -> PrefillOutput:
    last_logits, state = M.prefill(params, batch, cfg, max_seq=max_seq,
                                   kv_block=kv_block)
    if cfg.encoder_only:
        # encode-and-ship: "first_token" is the argmax unit per frame start
        first = jnp.argmax(last_logits[:, 0], axis=-1).astype(jnp.int32) \
            if last_logits.ndim == 3 else jnp.zeros((last_logits.shape[0],), jnp.int32)
        return PrefillOutput(first_token=first, last_logits=last_logits[:, -1]
                             if last_logits.ndim == 3 else last_logits,
                             state=state)
    first = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    return PrefillOutput(first_token=first, last_logits=last_logits, state=state)


def make_prefill_fn(cfg: ArchConfig, max_seq: Optional[int] = None,
                    kv_block: int = 1024):
    """Jit-wrapped prefill step (static model config baked in)."""
    @jax.jit
    def fn(params, batch):
        with jax.named_scope("prefill"):
            return prefill_step(params, batch, cfg, max_seq=max_seq,
                                kv_block=kv_block)
    return fn
