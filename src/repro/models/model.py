"""Model assembly for all 10 assigned architectures (+ paper's Qwen3-32B).

One code path per family, all sharing the layer library:

  dense/moe/vlm/audio : scan-over-layers pre-norm transformer (GQA attention,
                        SwiGLU or MoE FFN); vlm/audio get stub frontends
  mla                 : scan-over-layers with MLA attention (latent KV cache)
  ssm                 : scan-over-layers Mamba-2 (SSD)
  hybrid              : scan over (rglru, rglru, local_attn) triples + leftover

Public API: init_params / abstract_params / forward / loss_fn / prefill /
decode_step / make_inputs / input_specs.  Everything is jit-friendly;
activation sharding is requested via repro.distributed.sharding.constrain
(no-op outside a policy context).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.distributed.sharding import constrain
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import scanctl
from repro.models import ssm as SSM
from repro.models.kvcache import DecodeState, init_cache, n_triples_extra


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def _init_dense_layer(key, cfg: ArchConfig):
    k1, k2 = jax.random.split(key)
    p = {
        "norm1": L.init_rms_norm(cfg.d_model),
        "norm2": L.init_rms_norm(cfg.d_model),
    }
    if cfg.mla is not None:
        p["attn"] = MLA.init_mla(k1, cfg.d_model, cfg.num_heads, cfg.mla)
    elif cfg.ssm is None:
        p["attn"] = L.init_attention(k1, cfg.d_model, cfg.num_heads,
                                     cfg.num_kv_heads, cfg.head_dim)
    if cfg.ssm is not None:
        p["mixer"] = SSM.init_mamba2(k1, cfg.d_model, cfg.ssm)
        del p["norm2"]
    elif cfg.moe is not None:
        p["ffn"] = MOE.init_moe(k2, cfg.d_model, cfg.moe)
    else:
        p["ffn"] = L.init_mlp(k2, cfg.d_model, cfg.d_ff)
    return p


def _init_triple(key, cfg: ArchConfig):
    h = cfg.hybrid
    u = h.lru_width or cfg.d_model
    ks = jax.random.split(key, 6)
    return {
        "rec": {
            "block": jax.vmap(lambda k: RG.init_rglru_block(
                k, cfg.d_model, u, h.conv_width))(ks[:2]),
            "norm": jnp.ones((2, cfg.d_model), jnp.bfloat16),
            "mlp": jax.vmap(lambda k: L.init_mlp(k, cfg.d_model, cfg.d_ff))(ks[2:4]),
            "norm_mlp": jnp.ones((2, cfg.d_model), jnp.bfloat16),
        },
        "attn": {
            "block": L.init_attention(ks[4], cfg.d_model, cfg.num_heads,
                                      cfg.num_kv_heads, cfg.head_dim),
            "norm": L.init_rms_norm(cfg.d_model),
            "mlp": L.init_mlp(ks[5], cfg.d_model, cfg.d_ff),
            "norm_mlp": L.init_rms_norm(cfg.d_model),
        },
    }


def init_params(cfg: ArchConfig, key: jax.Array) -> Dict:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p: Dict = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_size, d)) * 0.02).astype(jnp.bfloat16),
        "final_norm": L.init_rms_norm(d),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(ks[1], (d, cfg.vocab_size)) * 0.02).astype(jnp.bfloat16)
    if cfg.frontend is not None:
        p["frontend_proj"] = (jax.random.normal(
            ks[2], (cfg.frontend_dim, d)) * cfg.frontend_dim ** -0.5).astype(jnp.bfloat16)
    if cfg.hybrid is not None:
        nt, ne = n_triples_extra(cfg)
        tkeys = jax.random.split(ks[3], nt)
        p["triples"] = jax.vmap(lambda k: _init_triple(k, cfg))(tkeys)
        if ne:
            ekeys = jax.random.split(ks[4], ne)
            u = cfg.hybrid.lru_width or d
            p["extra"] = jax.vmap(lambda k: {
                "block": RG.init_rglru_block(k, d, u, cfg.hybrid.conv_width),
                "norm": L.init_rms_norm(d),
                "mlp": L.init_mlp(jax.random.fold_in(k, 1), d, cfg.d_ff),
                "norm_mlp": L.init_rms_norm(d),
            })(ekeys)
    else:
        lkeys = jax.random.split(ks[3], cfg.num_layers)
        p["layers"] = jax.vmap(lambda k: _init_dense_layer(k, cfg))(lkeys)
    return p


def abstract_params(cfg: ArchConfig) -> Dict:
    """ShapeDtypeStruct pytree — no allocation (dry-run path)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def embed_inputs(params, batch: Dict, cfg: ArchConfig) -> jax.Array:
    if cfg.frontend == "audio_frames":
        x = jnp.einsum("bsf,fd->bsd", batch["frames"].astype(jnp.bfloat16),
                       params["frontend_proj"])
        return constrain(x, "btd")
    tok = params["embed"][batch["tokens"]]  # gather over vocab-sharded table
    if cfg.frontend == "vision_patches":
        patches = jnp.einsum("bpf,fd->bpd", batch["patches"].astype(jnp.bfloat16),
                             params["frontend_proj"])
        tok = jnp.concatenate([patches, tok], axis=1)
    return constrain(tok, "btd")


def lm_logits(params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return constrain(logits, "logits")


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def _dense_layer_fwd(cfg: ArchConfig, lp, x, positions, kv_block=1024):
    """One transformer layer; returns (x, cache_entries, aux)."""
    h = L.rms_norm(x, lp["norm1"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.ssm is not None:
        mix_out, state = SSM.mamba2_forward(lp["mixer"], h, cfg.ssm, cfg.d_model)
        x = constrain(x + mix_out, "btd")
        return x, state, aux
    if cfg.mla is not None:
        attn_out, kv = MLA.mla_prefill(lp["attn"], h, positions, cfg.mla,
                                       cfg.rope_theta, kv_block=kv_block)
    else:
        q, k, v = L.attention_qkv(lp["attn"], h, positions, cfg.rope_theta)
        q = constrain(q, "bthd")
        k = constrain(k, "bthd")
        v = constrain(v, "bthd")
        o = L.chunked_attention(q, k, v, causal=not cfg.encoder_only,
                                kv_block=kv_block)
        attn_out = L.attention_out(lp["attn"], o)
        kv = (k, v)
    x = x + attn_out
    h2 = L.rms_norm(x, lp["norm2"], cfg.norm_eps)
    if cfg.moe is not None:
        ffn_out, aux = MOE.moe_ffn(lp["ffn"], h2, cfg.moe)
    else:
        ffn_out = L.mlp(lp["ffn"], h2)
    x = constrain(x + ffn_out, "btd")
    return x, kv, aux


def _triple_fwd(cfg: ArchConfig, tp, x, positions, window, kv_block=1024):
    """One (rglru, rglru, local_attn) hybrid triple; returns cache entries."""
    rec_states = []
    for i in range(2):
        sub = jax.tree.map(lambda a: a[i], tp["rec"])
        h = L.rms_norm(x, sub["norm"], cfg.norm_eps)
        out, st = RG.recurrent_block_forward(sub["block"], h)
        x = x + out
        h2 = L.rms_norm(x, sub["norm_mlp"], cfg.norm_eps)
        x = constrain(x + L.mlp(sub["mlp"], h2), "btd")
        rec_states.append(st)
    ap = tp["attn"]
    h = L.rms_norm(x, ap["norm"], cfg.norm_eps)
    q, k, v = L.attention_qkv(ap["block"], h, positions, cfg.rope_theta)
    q = constrain(q, "bthd")
    k = constrain(k, "bthd")
    v = constrain(v, "bthd")
    o = L.chunked_attention(q, k, v, causal=True, window=window, kv_block=kv_block)
    x = x + L.attention_out(ap["block"], o)
    h2 = L.rms_norm(x, ap["norm_mlp"], cfg.norm_eps)
    x = constrain(x + L.mlp(ap["mlp"], h2), "btd")
    w = min(window, k.shape[1])
    cache = {
        "attn_k": k[:, -w:], "attn_v": v[:, -w:],
        "rec_h": jnp.stack([s["h"] for s in rec_states]),
        "rec_conv": jnp.stack([s["conv"] for s in rec_states]),
    }
    return x, cache


def forward(params, batch: Dict, cfg: ArchConfig, *, kv_block: int = 1024,
            remat: bool = False, collect_cache: bool = False,
            logits_positions: str = "all"):
    """Full-sequence forward.  Returns (logits, cache_or_None, aux_loss).

    ``logits_positions='last'`` projects only the final position through the
    LM head — prefill needs just the first sampled token, and the full
    (B, S, V) logits chain is the single largest non-attention tensor in
    long-context prefill (EXPERIMENTS.md §Perf Cell A)."""
    x = embed_inputs(params, batch, cfg)
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]

    if cfg.hybrid is not None:
        window = cfg.hybrid.window

        def triple_step(carry, tp):
            h, _ = _triple_fwd(cfg, tp, carry, positions, window, kv_block)[0], None
            return h, None

        def triple_step_cache(carry, tp):
            h, cache = _triple_fwd(cfg, tp, carry, positions, window, kv_block)
            return h, cache

        step = triple_step_cache if collect_cache else triple_step
        if remat:
            step = jax.checkpoint(step)
        x, tcaches = scanctl.scan(step, x, params["triples"])
        extra_states = []
        ne = n_triples_extra(cfg)[1]
        for i in range(ne):
            ep = jax.tree.map(lambda a: a[i], params["extra"])
            h = L.rms_norm(x, ep["norm"], cfg.norm_eps)
            out, st = RG.recurrent_block_forward(ep["block"], h)
            x = x + out
            h2 = L.rms_norm(x, ep["norm_mlp"], cfg.norm_eps)
            x = constrain(x + L.mlp(ep["mlp"], h2), "btd")
            extra_states.append(st)
        cache = None
        if collect_cache:
            cache = dict(tcaches)
            if extra_states:
                cache["extra_h"] = jnp.stack([s["h"] for s in extra_states])
                cache["extra_conv"] = jnp.stack([s["conv"] for s in extra_states])
            else:
                cache["extra_h"] = jnp.zeros((0, b, x.shape[-1]), jnp.float32)
                cache["extra_conv"] = jnp.zeros(
                    (0, b, cfg.hybrid.conv_width - 1, x.shape[-1]), x.dtype)
        if logits_positions == "last":
            x = x[:, -1:]
        return lm_logits(params, x, cfg), cache, jnp.zeros((), jnp.float32)

    def layer_step(carry, lp):
        h, cache, aux = _dense_layer_fwd(cfg, lp, carry, positions, kv_block)
        return h, (cache if collect_cache else None, aux)

    step = jax.checkpoint(layer_step) if remat else layer_step
    x, (caches, auxs) = scanctl.scan(step, x, params["layers"])
    aux = jnp.sum(auxs)
    if logits_positions == "last":
        x = x[:, -1:]
    cache = None
    if collect_cache:
        if cfg.ssm is not None:
            cache = {"ssm": caches.ssm, "conv": caches.conv}
        elif cfg.mla is not None:
            cache = {"ckv": caches[0], "krope": caches[1]}
        else:
            cache = {"k": caches[0], "v": caches[1]}
    return lm_logits(params, x, cfg), cache, aux


def loss_fn(params, batch: Dict, cfg: ArchConfig, *, kv_block: int = 1024,
            remat: bool = True, aux_weight: float = 0.01):
    logits, _, aux = forward(params, batch, cfg, kv_block=kv_block, remat=remat)
    labels = batch["labels"]
    if cfg.frontend == "vision_patches":
        # frontend positions are prepended; score text positions only
        logits = logits[:, -labels.shape[1]:]
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
    loss = -jnp.mean(ll)
    return loss + aux_weight * aux, (loss, aux)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def prefill(params, batch: Dict, cfg: ArchConfig, *, max_seq: Optional[int] = None,
            kv_block: int = 1024) -> Tuple[jax.Array, DecodeState]:
    """Run the full prompt; return (last-position logits, decode state).

    For cache-positional families (dense/mla) the cache is padded to
    ``max_seq`` slots so decode can continue in place.

    Ragged batches: ``batch["lengths"]`` (B,) marks each row's true prompt
    length; rows are right-padded to a common S.  Causal attention keeps each
    row's valid prefix independent of its padding, so the fix is purely
    positional: last-token logits are gathered at ``lengths - 1`` (not at the
    padded position S-1) and ``cache_len`` starts at ``lengths`` (decode then
    overwrites the padding slots row by row).  Recurrent families (ssm /
    hybrid) absorb padding into their state and reject ragged input."""
    lengths = batch.get("lengths")
    if lengths is not None:
        if cfg.ssm is not None or cfg.hybrid is not None:
            raise ValueError(
                f"{cfg.name}: ragged prefill (batch['lengths']) needs a "
                "cache-positional family (dense/mla); recurrent state "
                "absorbs right-padding")
        if cfg.frontend is not None or cfg.encoder_only:
            raise ValueError("ragged prefill is token-decoder only")
    logits, cache, _ = forward(
        params, batch, cfg, kv_block=kv_block, collect_cache=True,
        logits_positions="all" if (cfg.encoder_only or lengths is not None)
        else "last")
    if cfg.frontend == "vision_patches":
        s = batch["tokens"].shape[1] + cfg.frontend_len
        b = batch["tokens"].shape[0]
    elif cfg.frontend == "audio_frames":
        s = batch["frames"].shape[1]
        b = batch["frames"].shape[0]
    else:
        b, s = batch["tokens"].shape
    if cfg.encoder_only:
        return logits, DecodeState(cache={}, cache_len=jnp.full((b,), s, jnp.int32))

    max_seq = max_seq or s
    if cfg.ssm is None and cfg.hybrid is None and max_seq > s:
        pad = max_seq - s
        def pad_seq(x):  # (L, B, S, ...) -> pad S
            widths = [(0, 0)] * x.ndim
            widths[2] = (0, pad)
            return jnp.pad(x, widths)
        cache = jax.tree.map(pad_seq, cache)
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
        return last, DecodeState(cache=cache, cache_len=lengths)
    return logits[:, -1], DecodeState(
        cache=cache, cache_len=jnp.full((b,), s, jnp.int32))


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

def _windowed_decode(ap, x, k_cache, v_cache, cache_len, cfg):
    """Sliding-window decode with a right-aligned shift-insert cache."""
    w = k_cache.shape[1]
    positions = cache_len[:, None]
    q = jnp.einsum("bsd,dhk->bshk", x, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, ap["wv"])
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    k_cache = jnp.concatenate([k_cache[:, 1:], k], axis=1)
    v_cache = jnp.concatenate([v_cache[:, 1:], v], axis=1)
    n_valid = jnp.minimum(cache_len + 1, w)                     # (B,)
    mask = jnp.arange(w)[None, :] >= (w - n_valid)[:, None]
    b, _, h, dq = q.shape
    g = h // k_cache.shape[2]
    qg = q.reshape(b, 1, k_cache.shape[2], g, dq)
    sc = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k_cache,
                    preferred_element_type=jnp.float32) / np.sqrt(dq)
    sc = jnp.where(mask[:, None, None, None, :], sc, L.NEG_INF)
    p_ = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", p_.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, 1, h, dq).astype(x.dtype)
    return L.attention_out(ap, o), k_cache, v_cache


def decode_step(params, tokens: jax.Array, state: DecodeState, cfg: ArchConfig
                ) -> Tuple[jax.Array, DecodeState]:
    """One autoregressive step.  tokens: (B, 1) int32 -> logits (B, V)."""
    if cfg.encoder_only:
        raise ValueError(f"{cfg.name} is encoder-only: no decode step")
    x = params["embed"][tokens]
    x = constrain(x, "btd")
    cache_len = state.cache_len
    cache = state.cache

    if cfg.hybrid is not None:
        window = cache["attn_k"].shape[2]

        def triple_step(carry, xs):
            h = carry
            tp, ck, cv, rh, rc = xs
            new_rh, new_rc = [], []
            for i in range(2):
                sub = jax.tree.map(lambda a: a[i], tp["rec"])
                hh = L.rms_norm(h, sub["norm"], cfg.norm_eps)
                out, st = RG.recurrent_block_step(
                    sub["block"], hh, {"h": rh[i], "conv": rc[i]})
                h = h + out
                hh2 = L.rms_norm(h, sub["norm_mlp"], cfg.norm_eps)
                h = h + L.mlp(sub["mlp"], hh2)
                new_rh.append(st["h"]); new_rc.append(st["conv"])
            ap = tp["attn"]
            hh = L.rms_norm(h, ap["norm"], cfg.norm_eps)
            attn_out, ck, cv = _windowed_decode(ap["block"], hh, ck, cv, cache_len, cfg)
            h = h + attn_out
            hh2 = L.rms_norm(h, ap["norm_mlp"], cfg.norm_eps)
            h = h + L.mlp(ap["mlp"], hh2)
            return h, (ck, cv, jnp.stack(new_rh), jnp.stack(new_rc))

        x, (cks, cvs, rhs, rcs) = scanctl.scan(
            triple_step, x,
            (params["triples"], cache["attn_k"], cache["attn_v"],
             cache["rec_h"], cache["rec_conv"]))
        new_cache = dict(cache, attn_k=cks, attn_v=cvs, rec_h=rhs, rec_conv=rcs)
        ne = cache["extra_h"].shape[0]
        eh, ec = [], []
        for i in range(ne):
            ep = jax.tree.map(lambda a: a[i], params["extra"])
            hh = L.rms_norm(x, ep["norm"], cfg.norm_eps)
            out, st = RG.recurrent_block_step(
                ep["block"], hh, {"h": cache["extra_h"][i], "conv": cache["extra_conv"][i]})
            x = x + out
            hh2 = L.rms_norm(x, ep["norm_mlp"], cfg.norm_eps)
            x = x + L.mlp(ep["mlp"], hh2)
            eh.append(st["h"]); ec.append(st["conv"])
        if ne:
            new_cache["extra_h"] = jnp.stack(eh)
            new_cache["extra_conv"] = jnp.stack(ec)
    elif cfg.ssm is not None:
        def layer_step(carry, xs):
            lp, s_ssm, s_conv = xs
            h = L.rms_norm(carry, lp["norm1"], cfg.norm_eps)
            out, st = SSM.mamba2_decode(lp["mixer"], h, SSM.SSMState(s_ssm, s_conv),
                                        cfg.ssm, cfg.d_model)
            return carry + out, (st.ssm, st.conv)

        x, (ssms, convs) = scanctl.scan(
            layer_step, x, (params["layers"], cache["ssm"], cache["conv"]))
        new_cache = {"ssm": ssms, "conv": convs}
    elif cfg.mla is not None:
        def layer_step(carry, xs):
            lp, ckv, krope = xs
            h = L.rms_norm(carry, lp["norm1"], cfg.norm_eps)
            out, (ckv, krope) = MLA.mla_decode(lp["attn"], h, ckv, krope,
                                               cache_len, cfg.mla, cfg.rope_theta)
            h2 = L.rms_norm(carry + out, lp["norm2"], cfg.norm_eps)
            y = carry + out + (MOE.moe_ffn(lp["ffn"], h2, cfg.moe)[0]
                               if cfg.moe else L.mlp(lp["ffn"], h2))
            return constrain(y, "btd"), (ckv, krope)

        x, (ckvs, kropes) = scanctl.scan(
            layer_step, x, (params["layers"], cache["ckv"], cache["krope"]))
        new_cache = {"ckv": ckvs, "krope": kropes}
    else:
        def layer_step(carry, xs):
            lp, ck, cv = xs
            h = L.rms_norm(carry, lp["norm1"], cfg.norm_eps)
            out, (ck, cv) = L.decode_attention_block(
                lp["attn"], h, ck, cv, cache_len, cfg.rope_theta)
            y = carry + out
            h2 = L.rms_norm(y, lp["norm2"], cfg.norm_eps)
            ffn = (MOE.moe_ffn(lp["ffn"], h2, cfg.moe)[0] if cfg.moe
                   else L.mlp(lp["ffn"], h2))
            return constrain(y + ffn, "btd"), (ck, cv)

        x, (cks, cvs) = scanctl.scan(
            layer_step, x, (params["layers"], cache["k"], cache["v"]))
        new_cache = {"k": cks, "v": cvs}

    logits = lm_logits(params, x, cfg)[:, -1]
    return logits, DecodeState(cache=new_cache, cache_len=cache_len + 1)


def resident_decode_step(params, tokens: jax.Array, state, cfg: ArchConfig,
                         *, interpret: bool | None = None):
    """One autoregressive step over a compressed-resident cache.

    ``state`` is a ``kvpool.ResidentState``: the prefix lives as splitzip
    pages consumed directly by the fused Pallas attention kernel (one
    ``pallas_call`` per layer), and the step only grows the raw tail pages —
    the compressed pool is read-only here and tail flushes/recompression are
    host-side between steps (``KVPool.flush_full_tails``).  Dense-GQA and MLA
    families only; others decode raw-resident."""
    import dataclasses

    from repro.models import kvpool as KVP

    if cfg.encoder_only:
        raise ValueError(f"{cfg.name} is encoder-only: no decode step")
    g = state.geom
    x = params["embed"][tokens]
    x = constrain(x, "btd")
    cache_len = state.cache_len

    if cfg.mla is not None:
        cl, rl = state.leaves["ckv"], state.leaves["krope"]
        c_streams, r_streams = cl.streams(), rl.streams()
        fmt = g.leaf("ckv").fmt

        def layer_step(carry, xs):
            lp, pt_c, pt_r, tc, tr = xs
            h = L.rms_norm(carry, lp["norm1"], cfg.norm_eps)
            out, (tc, tr) = KVP.paged_mla_decode(
                lp["attn"], h, c_streams, r_streams, pt_c, pt_r, tc, tr,
                cache_len, cfg.mla, cfg.rope_theta, geom=g, fmt=fmt,
                interpret=interpret)
            h2 = L.rms_norm(carry + out, lp["norm2"], cfg.norm_eps)
            y = carry + out + (MOE.moe_ffn(lp["ffn"], h2, cfg.moe)[0]
                               if cfg.moe else L.mlp(lp["ffn"], h2))
            return constrain(y, "btd"), (tc, tr)

        x, (tcs, trs) = scanctl.scan(
            layer_step, x, (params["layers"], cl.page_table, rl.page_table,
                            cl.tail, rl.tail))
        new_leaves = {"ckv": dataclasses.replace(cl, tail=tcs),
                      "krope": dataclasses.replace(rl, tail=trs)}
    elif cfg.ssm is None and cfg.hybrid is None:
        kl, vl = state.leaves["k"], state.leaves["v"]
        k_streams, v_streams = kl.streams(), vl.streams()
        fmt = g.leaf("k").fmt

        def layer_step(carry, xs):
            lp, pt_k, pt_v, tk, tv = xs
            h = L.rms_norm(carry, lp["norm1"], cfg.norm_eps)
            out, (tk, tv) = KVP.paged_decode_attention_block(
                lp["attn"], h, k_streams, v_streams, pt_k, pt_v, tk, tv,
                cache_len, cfg.rope_theta, geom=g, fmt=fmt,
                interpret=interpret)
            y = carry + out
            h2 = L.rms_norm(y, lp["norm2"], cfg.norm_eps)
            ffn = (MOE.moe_ffn(lp["ffn"], h2, cfg.moe)[0] if cfg.moe
                   else L.mlp(lp["ffn"], h2))
            return constrain(y + ffn, "btd"), (tk, tv)

        x, (tks, tvs) = scanctl.scan(
            layer_step, x, (params["layers"], kl.page_table, vl.page_table,
                            kl.tail, vl.tail))
        new_leaves = {"k": dataclasses.replace(kl, tail=tks),
                      "v": dataclasses.replace(vl, tail=tvs)}
    else:
        raise ValueError(
            f"{cfg.name}: resident-compressed decode supports dense-GQA and "
            "MLA caches; ssm/hybrid decode raw-resident")

    logits = lm_logits(params, x, cfg)[:, -1]
    return logits, dataclasses.replace(
        state, leaves=new_leaves, cache_len=cache_len + 1)


# ---------------------------------------------------------------------------
# inputs (real + abstract)
# ---------------------------------------------------------------------------

def make_inputs(cfg: ArchConfig, shape: ShapeConfig, key=None, batch=None,
                seq=None) -> Dict:
    """Concrete input batch (smoke tests use reduced cfg + small shape)."""
    b = batch or shape.global_batch
    s = seq or shape.seq_len
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    out: Dict = {}
    if cfg.frontend == "audio_frames":
        out["frames"] = jax.random.normal(k1, (b, s, cfg.frontend_dim), jnp.bfloat16)
        out["labels"] = jax.random.randint(k2, (b, s), 0, cfg.vocab_size)
        return out
    if cfg.frontend == "vision_patches":
        s_text = s - cfg.frontend_len
        out["patches"] = jax.random.normal(k1, (b, cfg.frontend_len, cfg.frontend_dim),
                                           jnp.bfloat16)
        out["tokens"] = jax.random.randint(k2, (b, s_text), 0, cfg.vocab_size)
        out["labels"] = jax.random.randint(k3, (b, s_text), 0, cfg.vocab_size)
        return out
    out["tokens"] = jax.random.randint(k1, (b, s), 0, cfg.vocab_size)
    out["labels"] = jax.random.randint(k2, (b, s), 0, cfg.vocab_size)
    return out


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict:
    """ShapeDtypeStruct stand-ins for every model input (dry-run)."""
    b, s = shape.global_batch, shape.seq_len
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    if shape.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
    out = {}
    if cfg.frontend == "audio_frames":
        out["frames"] = jax.ShapeDtypeStruct((b, s, cfg.frontend_dim), bf16)
        out["labels"] = jax.ShapeDtypeStruct((b, s), i32)
        return out
    if cfg.frontend == "vision_patches":
        out["patches"] = jax.ShapeDtypeStruct((b, cfg.frontend_len, cfg.frontend_dim), bf16)
        out["tokens"] = jax.ShapeDtypeStruct((b, s - cfg.frontend_len), i32)
        out["labels"] = jax.ShapeDtypeStruct((b, s - cfg.frontend_len), i32)
        return out
    out["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
    out["labels"] = jax.ShapeDtypeStruct((b, s), i32)
    return out


def abstract_state(cfg: ArchConfig, batch: int, max_seq: int) -> DecodeState:
    """Abstract DecodeState for decode-shape dry-runs (cache at seq_len)."""
    cache = jax.eval_shape(lambda: init_cache(cfg, batch, max_seq))
    return DecodeState(
        cache=cache,
        cache_len=jax.ShapeDtypeStruct((batch,), jnp.int32),
    )
