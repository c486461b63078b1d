"""Plain float32 building blocks of the reference forward, and the loop
that runs it layer by layer.

``jax.numpy`` only; nothing of the program is imported. Every matrix product
runs at float32 ``highest`` precision (a TPU otherwise rounds float32 operands
to bf16 in one pass). Activations stay float32 from the embedding to the
logits; the weights are the bf16 values the recipe draws, widened to float32.

``mode="fp8"`` is the control of the check: both operands of every product
are rounded to float8 e4m3 after a per-tensor absmax scaling, the step below
the bf16 the configurations state.
"""

from __future__ import annotations

import functools
import json
from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import seeds

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
NEG = -1e30
Q_BLOCK = 256                 # query rows per attention block


def q8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 under a per-tensor absmax scale (the control)."""
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(eq: str, a: jax.Array, b: jax.Array, mode: str) -> jax.Array:
    if mode == "fp8":
        a, b = q8(a), q8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def draw(key, shape, scale) -> jax.Array:
    """One weight by the recipe: a float32 normal, scaled, stored as bf16."""
    return (jax.random.normal(key, shape) * scale).astype(
        jnp.bfloat16).astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope(x, pos, theta):
    """Rotate the two halves of the last axis; x (T, H, D), pos (T,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_attention(q, k, v, scale, mode):
    """softmax(q k^T * scale) v over a causal mask, in blocks of queries.

    q (T, H, Dq), k (T, Hkv, Dq), v (T, Hkv, Dv); query head h reads KV head
    h // (H / Hkv). T is a multiple of ``Q_BLOCK``."""
    t, h, dq = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    g = h // hkv
    qb = q.reshape(t // Q_BLOCK, Q_BLOCK, hkv, g, dq)

    def block(args):
        qi, i = args
        s = mm("qhgd,khd->hgqk", qi, k, mode) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[:, None] >= jnp.arange(t)[None, :], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        return mm("hgqk,khd->qhgd", p, v, mode)

    out = jax.lax.map(block, (qb, jnp.arange(t // Q_BLOCK)))
    return out.reshape(t, h, dv)


@functools.lru_cache(maxsize=None)
def _jitted(fam, conf_json: str, what: str, mode: str = ""):
    """One jitted function per (family, configuration, part, mode), kept
    across calls so a process that checks many seeds compiles once."""
    conf = json.loads(conf_json)
    if what == "layer":
        return jax.jit(functools.partial(fam.layer, conf, mode=mode))
    return jax.jit(functools.partial(getattr(fam, what), conf))


def logits_at(fam, conf: dict, seed: int, tokens: np.ndarray, first: int,
              count: int, modes: Sequence[str] = ("f32",)
              ) -> Dict[str, np.ndarray]:
    """Logits at positions ``first .. first + count - 1`` of each row.

    ``fam`` is a family module (``gqa``, ``mla``) with ``embed``,
    ``layer_weights``, ``layer`` and ``head``. Layer by layer, so only one
    layer's weights are held at a time; row by row inside a layer. Returns
    one (rows, count, vocab) float32 array per mode."""
    wkey = seeds.key(seed, "weights")
    rows, t = tokens.shape
    tp = -(-t // Q_BLOCK) * Q_BLOCK          # pad: causal, so no effect
    padded = np.zeros((rows, tp), np.int32)
    padded[:, :t] = tokens
    key = json.dumps(conf, sort_keys=True)
    x0 = _jitted(fam, key, "embed")(wkey, padded)
    xs = {m: [x0[r] for r in range(rows)] for m in modes}
    del x0
    weights = _jitted(fam, key, "layer_weights")
    layer: Dict[str, Callable] = {m: _jitted(fam, key, "layer", m)
                                  for m in modes}
    for i in range(conf["num_hidden_layers"]):
        w = weights(wkey, i)
        for m in modes:
            xs[m] = [layer[m](w, x) for x in xs[m]]
        del w
    head = _jitted(fam, key, "head")(wkey)
    eps = conf["rms_norm_eps"]
    out = {}
    for m in modes:
        hid = jnp.stack([x[first:first + count] for x in xs[m]])
        out[m] = np.asarray(mm("rtd,dv->rtv", rms_norm(hid, 1.0, eps), head,
                               m))
    return out
