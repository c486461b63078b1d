"""Paper-style Fig. 6 (ISSUE 8): decode-worker capacity and attention step
throughput with compressed-resident KV.

Two claims, measured against the repo's own pool/kernel (models/kvpool.py,
kernels/splitzip_attention.py):

1. **Capacity** — max concurrent sequences at a fixed decode-worker HBM
   budget.  The compressed-resident footprint comes from the pool's OWN
   page accounting (``KVPool.page_bytes`` — dense streams + escape
   metadata — plus the always-allocated raw tail page and page tables);
   the raw footprint is the bf16 cache.  At >=4096-token context the paged
   format holds >=1.25x the sequences of raw residency.
2. **Step throughput** — one fused-attention decode step over compressed
   pages vs rehydrate-then-attend over the same admitted state.  CPU
   interpret-mode wall clock (table2's standing caveat applies: the
   structural win — no full-cache decompress materialization — carries the
   accelerator claim; CPU numbers are shape-level evidence, not GB/s).

The ``resident`` section is MERGED into ``benchmarks/BENCH_codec.json``
(read-modify-write: table2 owns the rest of the snapshot and overwrites the
file wholesale, so this module must never write anything but its own key).

Standalone: ``python -m benchmarks.fig6_resident_capacity``; smoke via
``SPLITZIP_BENCH_SMOKE=1`` (tiny context, no snapshot write).
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_fn
from repro.configs.base import get_config
from repro.core import codebook as cbm
from repro.core.backend import resolve_backend
from repro.models import kvcache as KC
from repro.models import kvpool as KVP
from repro.models import model as M
from repro.serving.plan import TransferConfig, TransferPlan
from repro.serving.session import encode_leaves

SMOKE = bool(int(os.environ.get("SPLITZIP_BENCH_SMOKE", "0")))
SNAPSHOT_PATH = os.path.join(os.path.dirname(__file__), "BENCH_codec.json")

HBM_BYTES = 16 << 30          # per-decode-worker KV budget
CONTEXT = 4096                # tokens per resident sequence (paper regime)


def _capacity_row(arch: str) -> dict:
    """Sequences-at-fixed-HBM from the pool's real page accounting."""
    cfg = get_config(arch)
    chunk = 1024
    # geometry only: shapes drive every byte count, so one-token-deep
    # abstract leaves suffice — no giant cache materialization
    cache = jax.eval_shape(
        lambda: KC.init_cache(cfg, 1, max(CONTEXT, 8 * chunk)))
    tp = KVP.tokens_per_page_for(cache, chunk)
    ctx = -(-CONTEXT // tp) * tp

    raw_per_seq = comp_per_seq = 0
    for key, leaf in cache.items():
        m = int(np.prod(leaf.shape[3:])) if len(leaf.shape) > 3 else 1
        L = leaf.shape[0]
        itemsize = jnp.dtype(leaf.dtype).itemsize
        raw_per_seq += L * ctx * m * itemsize
        pe = tp * m
        cap = max(8, pe // KVP.ESC_SLOT_PER_ELEMS)
        page_bytes = pe + pe // 2 + cap * 3 + 4
        pages = ctx // tp
        tail = tp * m * itemsize                   # raw growth page
        table = pages * 4
        comp_per_seq += L * (pages * page_bytes + tail + table)

    seqs_raw = HBM_BYTES // raw_per_seq
    seqs_comp = HBM_BYTES // comp_per_seq
    return dict(
        arch=arch, context=ctx, tokens_per_page=tp,
        raw_mib_per_seq=round(raw_per_seq / 2**20, 2),
        resident_mib_per_seq=round(comp_per_seq / 2**20, 2),
        max_seqs_raw=int(seqs_raw), max_seqs_resident=int(seqs_comp),
        capacity_ratio=round(seqs_comp / max(1, seqs_raw), 4))


def _throughput_row() -> dict:
    """Fused step over pages vs rehydrate-then-attend, same admitted state."""
    cfg = get_config("smollm-135m").reduced()
    S = 128 if SMOKE else 512
    B = 2
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S - 8)), jnp.int32)
    _, st0 = M.prefill(params, {"tokens": toks}, cfg, max_seq=S)
    bits = np.concatenate(
        [np.asarray(jax.lax.bitcast_convert_type(v, jnp.uint16)).ravel()
         for v in st0.cache.values()])
    cb = cbm.calibrate(bits, k=16)
    backend = resolve_backend("xla", require_jittable=True)
    pool = KVP.KVPool.for_cache(st0.cache, cb, backend, chunk=1024,
                                page_bytes=2048)
    tc = TransferConfig(codebook=cb, chunk=1024, backend="xla")
    comp, _ = encode_leaves(TransferPlan.build(st0.cache, tc), st0.cache)
    rs = pool.admit_from_wire(comp, st0.cache_len)
    tok = jnp.zeros((B, 1), jnp.int32)

    fused = jax.jit(lambda t, s: M.resident_decode_step(
        params, t, s, cfg)[0])

    def rehydrated_step(t, s):
        cache = pool.rehydrate(s)                  # full-cache decompress
        st = KC.DecodeState(cache=cache, cache_len=s.cache_len)
        return M.decode_step(params, t, st, cfg)[0]

    rehydrate = jax.jit(rehydrated_step)
    reps = 2 if SMOKE else 5
    t_fused, _ = time_fn(lambda: jax.block_until_ready(fused(tok, rs)),
                         repeats=reps, warmup=1)
    t_reh, _ = time_fn(lambda: jax.block_until_ready(rehydrate(tok, rs)),
                       repeats=reps, warmup=1)
    return dict(
        context=S, batch=B,
        fused_step_ms=round(t_fused * 1e3, 3),
        rehydrate_step_ms=round(t_reh * 1e3, 3),
        fused_vs_rehydrate=round(t_reh / max(t_fused, 1e-12), 4),
        note="CPU interpret-mode wall clock; structural claim is "
             "zero full-cache decompress in the fused path")


def run(emit) -> None:
    caps = [_capacity_row(a) for a in ("qwen3-32b", "smollm-135m")]
    for row in caps:
        emit("fig6", f"capacity/{row['arch']}", dict(row))
    thr = _throughput_row()
    emit("fig6", "step_throughput", dict(thr))

    head = caps[0]
    assert head["capacity_ratio"] >= 1.25, (
        f"resident capacity ratio {head['capacity_ratio']} < 1.25 at "
        f"{head['context']}-token context")

    if SMOKE:
        emit("fig6", "snapshot", dict(skipped="smoke mode"))
        return
    # merge (never overwrite) the shared snapshot
    snapshot = {}
    if os.path.exists(SNAPSHOT_PATH):
        with open(SNAPSHOT_PATH) as f:
            snapshot = json.load(f)
    snapshot["resident"] = {
        "hbm_gib": HBM_BYTES >> 30,
        "capacity": {row["arch"]: {k: v for k, v in row.items()
                                   if k != "arch"} for row in caps},
        "step_throughput": thr,
    }
    with open(SNAPSHOT_PATH, "w") as f:
        json.dump(snapshot, f, indent=1, sort_keys=True)
        f.write("\n")
    emit("fig6", "snapshot", dict(path=os.path.relpath(SNAPSHOT_PATH)))


def main() -> None:
    def emit(table, row, values):
        kv = ",".join(f"{k}={v}" for k, v in values.items())
        print(f"{table},{row},{kv}", flush=True)

    run(emit)


if __name__ == "__main__":
    main()
