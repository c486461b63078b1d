"""Paper Fig. 4: transmission-time breakdown on Qwen3-32B, batch 16, at
sequence lengths 2K / 16K / 64K under the RoCE 4x200G configuration
(700 Gb/s effective -> 87.5 GB/s).  Expected: compressed transfer dominates
at long context; encode/decode shares shrink as payload grows relative to
fixed overheads.

Paper-internal consistency note (EXPERIMENTS.md §Reproduction): the paper's
stated native times imply an effective link of ~155 GB/s (not the stated
87.5), and its 5.7%/1.4% encode/decode shares imply the codec ran sharded
across the serving GPUs (aggregate ≈ n_gpu x 613 GB/s).  Both knobs are
exposed here: the `stated` rows use the paper's stated constants (single-GPU
codec, 87.5 GB/s); the `fitted` rows use link_bw/codec_parallelism fitted to
the paper's own Fig. 4 numbers, and reproduce them closely.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.common import bench_config, generate_kv_bits, pooled_bits
from repro.configs.base import get_config
from repro.core import codebook as cbm
from repro.core.pipeline import CodecProfile, pipelined_transfer_time
from repro.serving.plan import TransferConfig, transfer_report

N_CHUNKS = 8  # pipelined-engine granularity (TransferPlan n_chunks)

FIXED = 5e-3  # per-transfer fixed cost at batch granularity

# (label, effective link bandwidth, codec parallelism)
SETTINGS = (
    ("stated", 87.5e9, 1),    # paper's stated constants, single-GPU codec
    ("fitted", 155e9, 8),     # fitted to the paper's own Fig. 4 numbers
)

PAPER_FIG4 = {2048: (56.5, 53.1), 16384: (441.4, 353.8), 65536: (1749.3, 1397.0)}


def run(emit) -> None:
    cfg = get_config("qwen3-32b")
    bits = pooled_bits(generate_kv_bits(bench_config("qwen3-32b"),
                                        seq=256, batch=2))
    cb = cbm.calibrate([bits], k=16)
    # measured rho via the byte-exact host backend of the codec registry
    be = TransferConfig(codebook=cb, backend="wire").get_backend()
    ct = be.encode(jnp.asarray(bits), cb)
    rho = be.raw_bytes(ct) / float(be.wire_bytes(ct))
    bpt = cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim * 2
    for label, link_bw, par in SETTINGS:
        profile = CodecProfile(g_enc=613.3e9 * par, g_dec=2181.8e9 * par,
                               ratio=rho, link_bw=link_bw,
                               fixed_overhead_s=FIXED)
        for seq in (2048, 16384, 65536):
            raw = float(bpt) * seq * 16
            rep = transfer_report(raw, raw / rho, profile)
            total = rep.t_splitzip
            # chunked pipelined engine: encode/transfer/decode overlap
            t_pipe = pipelined_transfer_time(raw, profile, N_CHUNKS)
            row = dict(
                t_native_ms=round(rep.t_native * 1e3, 2),
                t_splitzip_ms=round(total * 1e3, 2),
                t_pipelined_ms=round(t_pipe * 1e3, 2),
                frac_encode=round(rep.t_encode / total, 4),
                frac_transfer=round(rep.t_transfer / total, 4),
                frac_decode=round(rep.t_decode / total, 4),
                speedup=round(rep.speedup, 4),
                speedup_pipelined=round(rep.t_native / t_pipe, 4))
            if label == "fitted":
                row["paper_native_ms"], row["paper_splitzip_ms"] = PAPER_FIG4[seq]
            emit("fig4", f"{label}/seq{seq}", row)
