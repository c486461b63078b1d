"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the full disaggregated pipeline in local mode: calibrate a SplitZip
codebook on this model's real KV activations, then prefill -> compressed
transfer -> decode for a batch of synthetic prompts, reporting transfer
ratio, codec health, and (analytic) transfer-time speedup under a chosen
link bandwidth.

``--codec-backend`` selects the codec implementation from the registry
(``auto`` | ``xla`` | ``pallas`` | ``wire``; ``auto`` — the default —
resolves to the fused Pallas kernels on TPU and the XLA reference
elsewhere); ``--n-chunks`` > 1 switches the transfer stage to the chunked
pipelined engine and reports per-chunk wire bytes; ``--compress-fp32``
routes fp32 recurrent states through the plan's hi/lo split (folded into
the chunked stream).  The engine resolves all of this ONCE into a
``TransferPlan`` (printed at the end as the per-leaf routing table) and
executes it through a ``TransferSession`` on every transfer.

``--profile`` selects the codec-profile source for the analytic transfer
report (:mod:`repro.core.profile`): ``paper`` (the H200 datasheet
constants, the fresh-checkout default), ``measured`` (the calibrated
``benchmarks/results/profiles.json``, measuring a small workload on the
spot when none exists), or an explicit ``profiles.json`` path.  The
resolved provenance is printed with the report, so "speedup at N Gb/s"
always says which cost model produced it.  See DESIGN.md's operator guide
for the full flag walk-through.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig, get_config
from repro.core import codebook as cbm
from repro.core.backend import available_backends
from repro.core.profile import resolve_profile
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving.engine import DisaggregatedEngine


def calibrate_on_model(cfg, params, seq=32, batch=2) -> cbm.Codebook:
    """Paper §3.3: one-time calibration on representative KV tensors."""
    shape = ShapeConfig("calib", seq_len=seq, global_batch=batch, kind="train")
    prompt = {k: v for k, v in M.make_inputs(cfg, shape, seq=seq).items()
              if k != "labels"}
    _, state = M.prefill(params, prompt, cfg, max_seq=seq)
    leaves = [np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16)).ravel()
              for x in jax.tree.leaves(state.cache) if x.dtype == jnp.bfloat16]
    if not leaves:
        return cbm.DEFAULT_BF16_CODEBOOK
    return cbm.calibrate(leaves, k=16)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--link-gbps", type=float, default=100.0,
                    help="simulated PD link (Gbit/s) for the analytic report")
    ap.add_argument("--no-compress", action="store_true")
    ap.add_argument("--codec-backend", default="auto",
                    choices=sorted(available_backends()),
                    help="codec backend registry key (core/backend.py); "
                         "'auto' resolves to the fused pallas kernels on "
                         "TPU, xla elsewhere")
    ap.add_argument("--n-chunks", type=int, default=1,
                    help=">1 => chunked pipelined transfer engine")
    ap.add_argument("--compress-fp32", action="store_true",
                    help="hi/lo-split-compress fp32 recurrent states "
                         "(SSM/RG-LRU) through the plan's fp32_hilo route")
    ap.add_argument("--profile", default="paper",
                    help="codec profile source for the analytic report: "
                         "'paper' (H200 datasheet constants), 'measured' "
                         "(calibrated benchmarks/results/profiles.json; "
                         "measures a small workload now if absent), or a "
                         "profiles.json path")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only; use the hubert "
                         "encode-and-ship example instead")

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    cb = calibrate_on_model(cfg, params)
    print(f"calibrated top-16 exponents: {cb.exponents}")

    profile = resolve_profile(args.profile,
                              link_bw=args.link_gbps * 1e9 / 8,
                              backend=args.codec_backend)
    eng = DisaggregatedEngine(cfg, params, cb,
                              compress=not args.no_compress,
                              backend=args.codec_backend,
                              n_chunks=args.n_chunks,
                              compress_fp32=args.compress_fp32,
                              profile=profile)

    shape = ShapeConfig("serve", seq_len=args.prompt_len,
                        global_batch=args.batch, kind="prefill")
    prompt = {k: v for k, v in
              M.make_inputs(cfg, shape, seq=args.prompt_len).items()
              if k != "labels"}
    t0 = time.time()
    out = jax.block_until_ready(
        eng.generate(prompt, num_steps=args.new_tokens,
                     max_seq=args.prompt_len + args.new_tokens + 1))
    dt = time.time() - t0
    dev = jax.devices()[0]
    print(f"generated {out.shape} tokens in {dt:.2f}s, compilation included "
          f"(host wall clock, {dev.platform} {dev.device_kind})")
    print(f"cache raw bytes      : {eng.stats.raw_cache_bytes:,.0f}")
    print(f"cache wire bytes     : {eng.stats.wire_bytes:,.0f}")
    print(f"transfer ratio       : {eng.stats.transfer_ratio:.3f}x")
    print(f"codec ok (no overflow): {eng.stats.codec_ok}")
    resolved = eng.tc.get_backend().name
    print(f"codec backend        : {args.codec_backend}"
          + (f" (resolved: {resolved})" if args.codec_backend == "auto" else ""))
    print(eng.describe_plan())
    if eng.stats.chunk_retries:
        print(f"capacity schedule    : {eng.stats.chunk_retries} units "
              f"retried, {eng.stats.chunk_retry_steps} extra encode attempts")
    if eng.stats.chunk_wire_bytes:
        per = eng.stats.chunk_wire_bytes
        print(f"pipelined chunks     : {len(per)} shipped "
              f"(requested {args.n_chunks}; alignment to the codec chunk can "
              f"produce fewer) — per-chunk wire bytes "
              f"min={min(per):,.0f} max={max(per):,.0f}")
    rep = eng.transfer_report()
    if rep:
        print(f"analytic transfer    : native {rep.t_native*1e3:.2f} ms -> "
              f"splitzip {rep.t_splitzip*1e3:.2f} ms "
              f"({rep.speedup:.3f}x at {args.link_gbps:.0f} Gb/s, "
              f"profile: {profile.source})")


if __name__ == "__main__":
    main()
