"""Smoke run of the disaggregated serving path on TPU chips.

    python chip_smoke.py             # one chip: the served path end to end
    python chip_smoke.py --chips 4   # four chips: the KV hop between chips

Model: qwen3-32b at its published widths (d_model 5120, 64 query heads and
8 KV heads of 128, d_ff 25600, vocabulary 151936), cut to 8 of its 64
layers, with random bf16 weights from a fixed seed.  Traffic: 4 prompts of
2048 tokens, 16 new tokens each (long input, short answer).

One chip, through ``DisaggregatedEngine(backend="auto")`` as
``launch/serve.py`` builds it, calling the stages ``generate`` runs:

1. device: the first device is a TPU and the codec backend resolves to the
   compiled Pallas kernels;
2. compressed transfer: the received cache equals the prefill cache bit for
   bit (uint16 views); prints the wire ratio;
3. raw decode: the tokens equal those of an engine that does not compress;
4. compressed-resident decode: the cache is admitted into pages without
   demotion and decoded by the compiled paged attention kernel, whose
   layer-0 output is checked against the raw cache; prints how many tokens
   agree with the raw path.

Four chips (``--chips 4``): the prefill cache sits on pod 0 of a (pod 2,
data 2) mesh, pod 1 holds zeros; a mesh ``TransferPlan`` ships it to pod 1
by ``ppermute``, once compressed and once raw.  Both must arrive bit for bit
on chips other than the source's.

Each phase runs twice after ``jax.block_until_ready``: the first call is
set-up (compilation included), the second is the phase time, on the host
clock.  Any failure raises, so the run exits non-zero and prints no result;
so does a machine without a TPU.  The last line of stdout is one JSON
object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH, LAYERS = "qwen3-32b", 8
BATCH, PROMPT, NEW_TOKENS = 4, 2048, 16
SEED = 0


class SmokeFailure(AssertionError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def timed(fn):
    """(result, seconds) of ``fn()`` on the host clock, device work done."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def twice(label: str, what: str, fn):
    """First call = set-up (compilation included), second = the phase."""
    _, cold = timed(fn)
    out, warm = timed(fn)
    print(f"[{label}] {what}: {warm * 1e3:.3f} ms (first call {cold:.3f} s, "
          f"compilation included)", flush=True)
    return out, cold, warm


def same_bits(a, b) -> bool:
    """Bitwise equality of two pytrees of arrays (no float compare: NaN)."""
    import jax
    import jax.numpy as jnp
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)

    def u(x):
        return jax.lax.bitcast_convert_type(
            x, {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize])
    return len(la) == len(lb) and all(
        x.shape == y.shape and x.dtype == y.dtype
        and bool(jnp.array_equal(u(x), u(y))) for x, y in zip(la, lb))


def build(label: str):
    """Config, weights, calibrated codebook and prompts (set-up)."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.launch.serve import calibrate_on_model
    from repro.models import model as M

    cfg = dataclasses.replace(get_config(ARCH), num_layers=LAYERS)
    print(f"model: {ARCH} at published widths (d_model {cfg.d_model}, "
          f"{cfg.num_heads} query heads, {cfg.num_kv_heads} KV heads of "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}); "
          f"{LAYERS} of 64 layers; the rest would be further pipeline stages",
          flush=True)
    print(f"weights: {2 * cfg.param_count() / 1e9:.2f} GB bf16, random from "
          f"seed {SEED}; traffic: {BATCH} prompts x {PROMPT} tokens, "
          f"{NEW_TOKENS} new tokens", flush=True)
    params, t = timed(lambda: jax.jit(
        lambda k: M.init_params(cfg, k))(jax.random.PRNGKey(SEED)))
    print(f"[{label}] set-up: weights drawn on the device in {t:.3f} s",
          flush=True)
    cb, t = timed(lambda: calibrate_on_model(cfg, params))
    print(f"[{label}] set-up: codebook calibrated in {t:.3f} s: "
          f"{cb.exponents}", flush=True)
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                                (BATCH, PROMPT), 0, cfg.vocab_size, jnp.int32)
    return cfg, params, cb, {"tokens": tokens}


def attention_witness(cfg, cache, rst, exponents, chunk: int, tp: int):
    """Layer 0's compiled ``paged_gqa_attention`` against the raw cache.

    Zero queries make every probability exactly 1, so the kernel returns
    each KV head's mean over the paged tokens.  Given one leaf's streams as
    both K and V, it checks every decoded element of that leaf up to f32
    summation order: the error stays within 2^-14 of the mean magnitude.
    Random queries then check scores and softmax against the model's own
    attention over the raw cache (``layers.decode_attention``): the two
    round the probabilities to bf16 at different maxima, so they may differ
    by 2^-6 of the largest |v|.  Returns the two error ratios."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import splitzip_attention as SA
    from repro.models import layers as Ly

    b, h, hkv, hd = BATCH, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lens = rst.cache_len
    n_tok = lens // tp * tp                            # tokens in full pages
    valid = jnp.arange(cache["k"].shape[2])[None, :] < n_tok[:, None]

    def attend(q, ks, vs):
        acc, _, l = SA.paged_gqa_attention(
            q, ks.streams(), vs.streams(), ks.page_table[0],
            vs.page_table[0], lens, exponents=exponents, chunk=chunk,
            tokens_per_page=tp, hkv=hkv)
        return acc / l[..., None]

    def raw(key):                                      # (B, S, hkv, hd) f32
        return jnp.where(valid[..., None, None],
                         cache[key][0].astype(jnp.float32), 0.0)

    q0 = jnp.zeros((b, 1, h, hd), jnp.bfloat16)
    mean_err = 0.0
    for key in ("k", "v"):
        leaf = rst.leaves[key]
        got = attend(q0, leaf, leaf).reshape(b, hkv, h // hkv, hd)
        x = raw(key)
        want = (x.sum(1) / n_tok[:, None, None])[:, :, None]
        mag = (jnp.abs(x).sum(1) / n_tok[:, None, None])[:, :, None]
        err = jnp.where(mag > 0, jnp.abs(got - want) / jnp.where(
            mag > 0, mag, 1.0), jnp.abs(got - want))
        mean_err = max(mean_err, float(err.max()))

    q = jax.random.normal(jax.random.PRNGKey(SEED + 2), (b, 1, h, hd),
                          jnp.float32).astype(jnp.bfloat16)
    got = attend(q, rst.leaves["k"], rst.leaves["v"])
    want = Ly.decode_attention(q, cache["k"][0], cache["v"][0], n_tok)
    attn_err = float(jnp.abs(got - want.astype(jnp.float32)).max()
                     / jnp.abs(raw("v")).max())
    return mean_err, attn_err


def first_step_logits(cfg, params, tok, raw_state, resident_step, rst):
    """The first decode step over the raw and the resident cache: the
    relative L2 distance of the two logit vectors, and on how many rows
    their argmax agrees.  A measurement, not a check: each layer rounds its
    output to bf16, so attentions that sum in different orders part by bf16
    ulps that the random-weight layers above carry on; a kernel fault shows
    in ``attention_witness``."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M

    raw = jax.jit(lambda p, t, s: M.decode_step(p, t, s, cfg)[0])(
        params, tok, raw_state).astype(jnp.float32)
    res = resident_step(params, tok, rst)[0].astype(jnp.float32)
    rel = float(jnp.linalg.norm(res - raw) / jnp.linalg.norm(raw))
    return rel, int((res.argmax(-1) == raw.argmax(-1)).sum())


def one_chip(label: str) -> None:
    import jax
    from repro.kernels import auto_interpret
    from repro.models import model as M
    from repro.models.kvpool import ResidentState
    from repro.serving.engine import DisaggregatedEngine

    t_start = time.perf_counter()
    cfg, params, cb, batch = build(label)
    setup = [time.perf_counter() - t_start]

    print("phase 1: device", flush=True)
    eng = DisaggregatedEngine(cfg, params, cb, backend="auto")
    backend = eng.tc.get_backend().name
    print(f"  codec backend auto -> {backend}", flush=True)
    check(backend == "pallas", "codec backend resolves to pallas")
    check(not auto_interpret(None), "Pallas kernels compile (no interpreter)")
    res = DisaggregatedEngine(cfg, params, cb, backend="auto",
                              resident="compressed")
    tp = res.resident_tokens_per_page()
    max_seq = -(-(PROMPT + 1 + NEW_TOKENS) // tp) * tp

    pre, cold, warm = twice(label, "prefill",
                            lambda: eng.prefill(batch, max_seq=max_seq))
    setup.append(cold - warm)

    print("phase 2: compressed transfer", flush=True)
    got, cold, warm = twice(label, "compressed transfer",
                            lambda: eng.transfer(pre.state))
    setup.append(cold - warm)
    print(f"  wire ratio {eng.stats.transfer_ratio:.4f}x "
          f"({eng.stats.raw_cache_bytes / 2:,.0f} raw bytes per transfer), "
          f"codec_ok {eng.stats.codec_ok}, capacity retries "
          f"{eng.stats.chunk_retries}", flush=True)
    check(eng.stats.codec_ok, "codec_ok")
    check(same_bits(got.cache, pre.state.cache),
          "received cache equals the prefill cache bit for bit")

    print("phase 3: raw decode", flush=True)
    toks, cold, warm = twice(label, "raw decode",
                             lambda: eng.decode(pre.first_token, got,
                                                NEW_TOKENS))
    setup.append(cold - warm)
    ref = DisaggregatedEngine(cfg, params, cb, compress=False,
                              backend="auto")
    pre_r = ref.prefill(batch, max_seq=max_seq)
    toks_r = ref.decode(pre_r.first_token, ref.transfer(pre_r.state),
                        NEW_TOKENS)
    check(same_bits(toks, toks_r),
          "raw-decode tokens equal the uncompressed engine's")

    print("phase 4: compressed-resident decode", flush=True)
    rst, cold, warm = twice(label, "transfer + resident admit",
                            lambda: res.transfer(pre.state))
    setup.append(cold - warm)
    check(isinstance(rst, ResidentState) and res.stats.resident_admits >= 1,
          f"cache admitted compressed-resident "
          f"({res.stats.resident_admits} admits, resident ratio "
          f"{res.stats.resident_ratio:.4f}x)")
    step = jax.jit(lambda p, t, s: M.resident_decode_step(p, t, s, cfg))
    tok = pre.first_token[:, None]
    check("tpu_custom_call" in step.lower(params, tok, rst).as_text(),
          "resident step runs the compiled paged_gqa_attention kernel")
    mean_err, attn_err = attention_witness(
        cfg, pre.state.cache, rst, tuple(cb.exponents), res.tc.chunk, tp)
    check(mean_err <= 2.0 ** -14,
          f"layer 0 kernel, zero queries: per-head means of K and V equal "
          f"the raw cache's within {mean_err:.3e} of their magnitude "
          f"(bound 2^-14)")
    check(attn_err <= 2.0 ** -6,
          f"layer 0 kernel, random queries: attention within "
          f"{attn_err:.3e} of max|v| of the model's over the raw cache "
          f"(bound 2^-6)")
    rel, top = first_step_logits(cfg, params, tok, got, step, rst)
    print(f"  first step: resident logits {rel:.3e} (relative L2) from raw "
          f"decode's over the same cache; argmax agrees on {top} of {BATCH} "
          f"rows", flush=True)
    toks_c, cold, warm = twice(label, "resident decode",
                               lambda: res.decode(pre.first_token, rst,
                                                  NEW_TOKENS))
    setup.append(cold - warm)
    check(res.stats.resident_demotions == 0, "0 demotions")
    agree = int((toks_c == toks).sum())
    print(f"  resident tokens agreeing with raw decode: {agree} of "
          f"{toks.size}", flush=True)
    print(f"[{label}] set-up: {sum(setup):.3f} s (weights, calibration and "
          f"first calls; {os.environ.get('JAX_COMPILATION_CACHE_DIR') or 'default'}"
          f" compile cache)", flush=True)


def four_chips(label: str) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.analysis.roofline import collective_bytes_from_hlo
    from repro.launch.mesh import make_mesh
    from repro.serving.engine import DisaggregatedEngine
    from repro.serving.plan import TransferConfig, TransferPlan

    devs = jax.devices()
    check(len(devs) == 4 and len({d.id for d in devs}) == 4
          and all(d.platform == "tpu" for d in devs), "4 distinct TPU chips")
    mesh = make_mesh((2, 2), ("pod", "data"))
    src, dst = list(mesh.devices[0]), list(mesh.devices[1])
    print(f"  mesh pod 0 (prefill) = chips {[d.id for d in src]}, pod 1 "
          f"(decode) = chips {[d.id for d in dst]}", flush=True)

    with jax.default_device(src[0]):      # input only: not timed
        cfg, params, cb, batch = build(label)
        eng = DisaggregatedEngine(cfg, params, cb, backend="auto")
        cache = eng.prefill(batch, max_seq=PROMPT + 1 + NEW_TOKENS).state.cache

    spec = P(None, "data")
    sharding = NamedSharding(mesh, spec)

    def on_pod0(x):
        """Global array: pod 0's chips hold the cache, pod 1's zeros."""
        host = np.asarray(x)
        parts = [jax.device_put(host[idx] if d in src
                                else np.zeros_like(host[idx]), d)
                 for d, idx in sharding.devices_indices_map(x.shape).items()]
        return jax.make_array_from_single_device_arrays(x.shape, sharding,
                                                        parts)

    placed = jax.tree.map(on_pod0, cache)
    for compress in (True, False):
        route = "compressed" if compress else "raw"
        tc = TransferConfig(codebook=cb, backend="auto", enabled=compress)
        plan = TransferPlan.build(placed, tc, mesh=mesh)
        sess = plan.session()
        print(f"route {route}: {plan.backend.name} backend", flush=True)
        out, _, _ = twice(f"{label} x4", f"mesh transfer, {route}",
                          lambda: sess.transfer(placed, select_dst=False))
        moved = collective_bytes_from_hlo(sess.lower_hlo(placed)).get(
            "collective-permute", 0)
        print(f"  ICI collective-permute bytes: {moved:,}", flush=True)
        for x, y in zip(jax.tree.leaves(cache), jax.tree.leaves(out)):
            want = np.asarray(x)
            dst_shards = [s for s in y.addressable_shards
                          if s.index[0] == slice(1, 2, None)]
            check(len(dst_shards) == 2
                  and {s.device for s in dst_shards} == set(dst)
                  and not {s.device for s in dst_shards} & set(src),
                  f"{route}: destination shards on pod 1 chips, apart from "
                  f"the source")
            same = all(np.array_equal(
                np.asarray(s.data)[0].view(np.uint16),
                want[s.index[1:]].view(np.uint16)) for s in dst_shards)
            check(same, f"{route}: {want.shape} leaf arrives bit for bit")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"no TPU found: jax.devices()[0] is {dev.platform!r}; this "
              "smoke runs on the chip only", file=sys.stderr)
        return 2
    label = f"{dev.platform} {dev.device_kind}"
    print(f"device: {label} x{len(devs)}; compile cache {cache_dir}",
          flush=True)
    if args.chips == 4:
        four_chips(label)
    else:
        one_chip(label)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
