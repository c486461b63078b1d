"""Decode worker: consumes a (transferred) cache and generates tokens.

``decode_loop`` runs N greedy steps with ``lax.scan`` so the whole generation
is one XLA program; ``serve_step`` is the single-token unit the dry-run
lowers for the decode_* shape cells.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.spans import span
from repro.models import model as M
from repro.models.kvcache import DecodeState


def serve_step(params, tokens: jax.Array, state: DecodeState, cfg: ArchConfig
               ) -> Tuple[jax.Array, DecodeState]:
    """One decode step: (B, 1) tokens -> ((B, V) logits, new state).
    This is the function the decode-shape dry-run cells lower."""
    return M.decode_step(params, tokens, state, cfg)


def decode_loop(params, first_token: jax.Array, state: DecodeState,
                cfg: ArchConfig, num_steps: int) -> Tuple[jax.Array, DecodeState]:
    """Greedy generation of ``num_steps`` tokens as a single scan program."""

    def step(carry, _):
        tok, st = carry
        logits, st = M.decode_step(params, tok[:, None], st, cfg)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, st), nxt

    (_, final_state), toks = jax.lax.scan(
        step, (first_token, state), None, length=num_steps)
    return toks.T, final_state  # (B, num_steps)


def make_decode_fn(cfg: ArchConfig, num_steps: int):
    @jax.jit
    def fn(params, first_token, state):
        with jax.named_scope("decode"):
            return decode_loop(params, first_token, state, cfg, num_steps)
    return fn


@functools.partial(jax.jit, static_argnums=(3, 4))
def _resident_step(params, tok, state, cfg, interpret):
    with jax.named_scope("resident_step"):
        return M.resident_decode_step(params, tok, state, cfg,
                                      interpret=interpret)


def resident_decode_loop(params, first_token: jax.Array, state, pool,
                         cfg: ArchConfig, num_steps: int, *,
                         interpret: bool | None = None):
    """Greedy generation over a compressed-resident cache.

    A Python loop of one reused jitted step (page tables and tails are
    fixed-shape, so every step hits the same executable) with a host-side
    tail recompression between steps: rows whose raw tail page filled are
    flushed into fresh compressed pages through the registered backend
    (``KVPool.flush_full_tails``).  The jitted step itself never touches the
    codec — the fused kernel decodes pages in-register.

    Escape overflow or pool exhaustion during a flush demotes the WHOLE
    batch: the pool rehydrates (bit-exact) to a raw ``DecodeState`` and the
    remaining steps run the classic decode loop.  Returns ``(tokens (B, N),
    final_state, demoted)``."""
    from repro.models.kvpool import ResidencyError

    tok = first_token
    toks = []
    st = state
    for i in range(num_steps):
        with span("resident.step", step=i):
            logits, st = _resident_step(params, tok[:, None], st, cfg,
                                        interpret)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks.append(tok)
        try:
            with span("resident.flush"):
                st = pool.flush_full_tails(st)
        except ResidencyError:
            cache = pool.rehydrate(st)
            dst = DecodeState(cache=cache, cache_len=st.cache_len)
            remaining = num_steps - (i + 1)
            if remaining:
                rest, dst = decode_loop(params, tok, dst, cfg, remaining)
                toks.extend(rest[:, j] for j in range(rest.shape[1]))
            return jnp.stack(toks, axis=1), dst, True
    return jnp.stack(toks, axis=1), st, False
