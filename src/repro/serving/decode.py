"""Decode worker: consumes a (transferred) cache and generates tokens.

``decode_loop`` runs N greedy steps with ``lax.scan`` so the whole generation
is one XLA program; ``serve_step`` is the single-token unit the dry-run
lowers for the decode_* shape cells.
"""

from __future__ import annotations

import dataclasses
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
    """One resident step; returns the logits, the new tails and lengths.

    The page pools pass through a step unchanged, and an output of a jit is
    a new buffer, so returning the whole state would copy every pool each
    step; the caller puts the tails and lengths back into its state."""
    with jax.named_scope("resident_step"):
        logits, new = M.resident_decode_step(params, tok, state, cfg,
                                             interpret=interpret)
    return logits, {k: leaf.tail for k, leaf in new.leaves.items()}, \
        new.cache_len


def resident_decode_loop(params, first_token: jax.Array, state, pool,
                         cfg: ArchConfig, num_steps: int, *,
                         interpret: bool | None = None):
    """Greedy generation over a compressed-resident cache.

    A Python loop of one reused jitted step (page tables and tails are
    fixed-shape, so every step hits the same executable) with a tail
    recompression between steps: rows whose raw tail page filled are
    flushed into fresh compressed pages through the registered backend
    (``KVPool.flush_full_tails``).  Every step adds one to every row's
    length, so the loop hands the flush the lengths after step ``i``
    (``state``'s, from ``KVPool.sync``, + ``i`` + 1) and an ordinary step
    reads nothing back.  The
    jitted step itself never touches the codec — the fused kernel decodes
    pages in-register.

    Escape overflow or pool exhaustion during a flush demotes the WHOLE
    batch: the pool rehydrates (bit-exact) to a raw ``DecodeState`` and the
    remaining steps run the classic decode loop.  Returns ``(tokens (B, N),
    final_state, demoted)``."""
    from repro.models.kvpool import ResidencyError

    tok = first_token
    toks = []
    st = state
    admitted = pool.sync(state)
    for i in range(num_steps):
        with span("resident.step", step=i):
            logits, tails, cache_len = _resident_step(
                params, tok[:, None], st, cfg, interpret)
            st = dataclasses.replace(st, cache_len=cache_len, leaves={
                k: dataclasses.replace(leaf, tail=tails[k])
                for k, leaf in st.leaves.items()})
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks.append(tok)
        try:
            with span("resident.flush"):
                st = pool.flush_full_tails(st, admitted + i + 1)
        except ResidencyError:
            cache = pool.rehydrate(st)
            dst = DecodeState(cache=cache, cache_len=st.cache_len)
            remaining = num_steps - (i + 1)
            if remaining:
                rest, dst = decode_loop(params, tok, dst, cfg, remaining)
                toks.extend(rest[:, j] for j in range(rest.shape[1]))
            return jnp.stack(toks, axis=1), dst, True
    return jnp.stack(toks, axis=1), st, False
