"""Paper Fig. 3: KV transfer time across sequence-length / batch-size sweeps
(Llama-3-8B-class and Qwen3-30B-A3B) — native vs SplitZip vs theoretical opt.

The per-token KV byte counts come from the FULL assigned configs (real cache
geometry); the compression ratio comes from the measured escape rate on this
repo's harvested KV activations; transfer times use the Appendix-A additive
model at the paper's RDMA-class link bandwidth.  Expected: speedup grows with
payload, saturating at 1.27-1.32x, approaching the theoretical rho.
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import bench_config, generate_kv_bits, pooled_bits
from repro.configs.base import get_config
from repro.core import codebook as cbm
from repro.core import wire
from repro.core.pipeline import CodecProfile
from repro.serving.plan import transfer_report

LINK_BW = 25e9        # 200 Gb/s RDMA-class per-transfer effective bandwidth
FIXED_OVERHEAD = 2e-4  # launch/registration overhead (short-payload regime)

SWEEPS = {
    "seq_b1": [(s, 1) for s in (512, 2048, 8192, 32768, 131072)],
    "seq_b16": [(s, 16) for s in (128, 1024, 8192, 65536)],
    "batch_s1024": [(1024, b) for b in (1, 16, 64, 256)],
    "batch_s32768": [(32768, b) for b in (1, 16, 128)],
}


def kv_bytes_per_token(cfg) -> int:
    if cfg.mla is not None:
        per = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
        return cfg.num_layers * per * 2
    return cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim * 2


def measured_ratio(arch: str) -> float:
    bits = pooled_bits(generate_kv_bits(bench_config(arch), seq=256, batch=2))
    cb = cbm.calibrate([bits], k=16)
    _, stats = wire.encode(bits, cb)
    return stats.ratio


def run(emit) -> None:
    for arch in ("llama3.2-3b", "qwen3-moe-30b-a3b"):
        cfg = get_config(arch)
        rho = measured_ratio(arch)
        bpt = kv_bytes_per_token(cfg)
        profile = CodecProfile(g_enc=613.3e9, g_dec=2181.8e9, ratio=rho,
                               link_bw=LINK_BW, fixed_overhead_s=FIXED_OVERHEAD)
        for sweep, points in SWEEPS.items():
            for seq, batch in points:
                raw = float(bpt) * seq * batch
                rep = transfer_report(raw, raw / rho, profile)
                emit("fig3", f"{arch}/{sweep}/s{seq}_b{batch}", dict(
                    raw_gb=round(raw / 1e9, 4),
                    t_native_ms=round(rep.t_native * 1e3, 3),
                    t_splitzip_ms=round(rep.t_splitzip * 1e3, 3),
                    speedup=round(rep.speedup, 4),
                    theoretical_opt=round(rho, 4)))
