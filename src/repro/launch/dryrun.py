import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver builds the jitted step (train_step for train
shapes, prefill_step for prefill shapes, serve_step for decode shapes) with
the production sharding policy, calls ``.lower(...).compile()`` against
ShapeDtypeStruct inputs (no allocation), and records:

  - memory_analysis()  (per-device bytes — proves it fits 16 GB v5e HBM)
  - cost_analysis()    (FLOPs / bytes for the roofline)
  - collective bytes   (parsed from post-SPMD HLO)

Usage:
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--out out.json]

Results are cached per cell in benchmarks/results/dryrun/ so the full sweep
is resumable.
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.analysis import roofline as RL
from repro.configs.base import SHAPES, ArchConfig, ShapeConfig, cells, get_config, shape_applicable
from repro.distributed.sharding import ShardingPolicy, use_policy
from repro.launch.mesh import describe, make_production_mesh
from repro.models import model as M
from repro.training import optimizer as OPT
from repro.training import train_step as TS

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def _cell_id(arch: str, shape: str, multi_pod: bool, variant: str = "base") -> str:
    return f"{arch}__{shape}__{'pod2' if multi_pod else 'pod1'}__{variant}"


# policy knobs per variant (see EXPERIMENTS.md §Perf for the iteration log)
POLICY_VARIANTS = {
    "base": {},
    "noremat": {},
    "gradcomp": {},
    "fsdp": dict(fsdp=True),
    "moe": dict(moe_dispatch_sharding=True),
    "fsdp_moe": dict(fsdp=True, moe_dispatch_sharding=True),
    # PD-transfer variants (prefill shapes, multi-pod mesh): prefill + KV
    # handoff across the pod axis — raw / paper-chunked / global SplitZip
    "xfer_raw": dict(pd_disaggregated=True),
    "xfer_chunked": dict(pd_disaggregated=True),
    "xfer_global": dict(pd_disaggregated=True),
    # isolated KV handoff (no prefill compute): the paper's codec path alone,
    # so the DCN collective-permute bytes are exactly the wire payload
    "xferonly_raw": dict(pd_disaggregated=True),
    "xferonly_chunked": dict(pd_disaggregated=True),
    "xferonly_global": dict(pd_disaggregated=True),
    "xferonly_tight": dict(pd_disaggregated=True),
    "xferonly_fp32": dict(pd_disaggregated=True),
    # per-chunk ppermute with double-buffering (TransferPlan n_chunks > 1)
    "xferonly_pipelined": dict(pd_disaggregated=True),
    # attention perf variants (EXPERIMENTS.md §Perf Cell A)
    "attn_bf16": {},
    "attn_kv4096": {},
    "attn_bf16_kv4096": {},
}

# attention-knob overrides per variant (threaded through models/layers.py)
ATTN_VARIANTS = {
    "attn_bf16": dict(score_dtype="bfloat16"),
    "attn_kv4096": dict(kv_block=4096),
    "attn_bf16_kv4096": dict(score_dtype="bfloat16", kv_block=4096),
}


def make_policy(mesh, variant: str) -> ShardingPolicy:
    return ShardingPolicy(mesh, **POLICY_VARIANTS.get(variant, {}))


def _cost_dict(compiled) -> dict:
    """compiled.cost_analysis() returns a dict on new jax and a one-element
    list of dicts on the 0.4.x line this repo pins — normalize to a dict."""
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost)


def _variant_ctx(variant: str):
    """Tracing-time context for attention-knob variants."""
    kw = ATTN_VARIANTS.get(variant)
    if not kw:
        import contextlib
        return contextlib.nullcontext()
    from repro.models import layers as LAY
    kw = dict(kw)
    if "score_dtype" in kw:
        kw["score_dtype"] = jnp.dtype(kw["score_dtype"])
    return LAY.attn_overrides(**kw)


def _transfer_config(variant: str):
    from repro.core.codebook import DEFAULT_BF16_CODEBOOK as cb
    from repro.serving import plan as T
    if variant.endswith("_raw"):
        return T.TransferConfig(codebook=cb, enabled=False)
    if variant.endswith("_chunked"):
        return T.TransferConfig(codebook=cb, chunk=1024, cap=64)
    if variant.endswith("_fp32"):
        # beyond-paper: also hi/lo-split-compress fp32 recurrent states
        return T.TransferConfig(codebook=cb, layout="global",
                                global_budget=0.0025, compress_fp32=True)
    if variant.endswith("_pipelined"):
        # chunked mesh path: per-chunk ppermute, double-buffered
        return T.TransferConfig(codebook=cb, chunk=1024, cap=64, n_chunks=8)
    if variant.endswith("_tight"):
        # 0.25% escape budget: 16x the paper's mean escape rate; overflow
        # still detected per tensor and falls back to raw
        return T.TransferConfig(codebook=cb, layout="global",
                                global_budget=0.0025)
    return T.TransferConfig(codebook=cb, layout="global")


def build_lowerable(cfg: ArchConfig, shape: ShapeConfig, policy: ShardingPolicy,
                    variant: str = "base"):
    """Returns (jitted_fn, example_args) ready for .lower(*args)."""
    mesh = policy.mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    remat = variant != "noremat"

    if variant.startswith("xferonly"):
        # isolated paper pipeline: cache in -> SplitZip -> DCN hop -> cache out
        if "pod" not in mesh.shape:
            raise ValueError("transfer variants need the multi-pod mesh")
        from repro.serving.plan import TransferPlan
        tc = _transfer_config(variant)
        state_abs = M.abstract_state(cfg, shape.global_batch, shape.seq_len)
        cache_abs = state_abs.cache
        specs = policy.cache_specs(cache_abs)
        cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                is_leaf=lambda x: isinstance(x, P))
        # the plan resolves routes/segments/specs once, from abstract shapes
        session = TransferPlan.build(cache_abs, tc, mesh=mesh,
                                     specs=specs).session()

        def fn(cache):
            with use_policy(policy):
                return session.transfer(cache, select_dst=False)

        jitted = jax.jit(fn, in_shardings=(cache_sh,))
        return jitted, (cache_abs,)

    if variant.startswith("xfer"):
        # paper's own pipeline: prefill -> SplitZip -> DCN hop -> decode pod
        if shape.kind != "prefill":
            raise ValueError("transfer variants apply to prefill shapes")
        if "pod" not in mesh.shape:
            raise ValueError("transfer variants need the multi-pod mesh")
        from repro.serving.plan import TransferPlan
        from repro.serving.prefill import prefill_step
        tc = _transfer_config(variant)

        params_abs = M.abstract_params(cfg)
        params_sh = policy.param_sharding(params_abs)
        batch_abs = M.input_specs(cfg, shape)
        batch_sh = jax.tree.map(
            lambda x: NamedSharding(mesh, policy.spec_for_activation(
                "tokens", tuple(x.shape))), batch_abs)

        def fn(params, batch):
            with use_policy(policy):
                out = prefill_step(params, batch, cfg, max_seq=shape.seq_len)
                cache = out.state.cache
                # plan built at trace time (shapes are static): one build
                # per compilation, executed by the session inside the jit
                session = TransferPlan.build(
                    cache, tc, mesh=mesh,
                    specs=policy.cache_specs(cache)).session()
                moved = session.transfer(cache)
                return out.first_token, moved

        jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh))
        return jitted, (params_abs, batch_abs)

    if shape.kind == "train":
        step = TS.make_train_step(cfg, OPT.AdamWConfig(), policy,
                                  grad_compress=(variant == "gradcomp"),
                                  remat=remat)
        state_abs = TS.abstract_state(cfg)
        batch_abs = M.input_specs(cfg, shape)
        jitted, (state_sh, batch_sh) = TS.jit_train_step(step, policy,
                                                         state_abs, batch_abs)
        return jitted, (state_abs, batch_abs)

    params_abs = M.abstract_params(cfg)
    params_sh = policy.param_sharding(params_abs)

    if shape.kind == "prefill":
        from repro.serving.prefill import prefill_step

        def fn(params, batch):
            with use_policy(policy):
                return prefill_step(params, batch, cfg, max_seq=shape.seq_len)

        batch_abs = M.input_specs(cfg, shape)
        batch_sh = jax.tree.map(
            lambda x: NamedSharding(mesh, policy.spec_for_activation(
                "tokens", tuple(x.shape))), batch_abs)
        jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh))
        return jitted, (params_abs, batch_abs)

    # decode: serve_step over a full-length cache
    from repro.models.kvcache import DecodeState
    from repro.serving.decode import serve_step

    state_abs = M.abstract_state(cfg, shape.global_batch, shape.seq_len)
    cache_sh = policy.cache_sharding(state_abs.cache)
    state_sh = DecodeState(cache=cache_sh,
                           cache_len=NamedSharding(mesh, P()))
    tok_abs = M.input_specs(cfg, shape)["tokens"]
    tok_sh = NamedSharding(mesh, policy.spec_for_activation(
        "tokens", tuple(tok_abs.shape)))

    def fn(params, tokens, state):
        with use_policy(policy):
            return serve_step(params, tokens, state, cfg)

    jitted = jax.jit(fn, in_shardings=(params_sh, tok_sh, state_sh),
                     donate_argnums=(2,))
    return jitted, (params_abs, tok_abs, state_abs)


def _extrapolation_depths(cfg: ArchConfig):
    """(L1, L2) reduced depths for the unrolled cost builds."""
    if cfg.hybrid is not None:
        pat = len(cfg.hybrid.pattern)
        return pat, 2 * pat
    return 2, 4


def measure_costs(cfg: ArchConfig, shape: ShapeConfig, policy: ShardingPolicy,
                  variant: str) -> dict:
    """flops / bytes / per-kind collective bytes, everything-unrolled build.

    XLA cost_analysis counts `while` bodies once (see models/scanctl.py), so
    a scanned 94-layer model reports ~1 layer of work.  We compile twice at
    reduced depths L1 < L2 with every scan unrolled and extrapolate linearly
    in L: per-layer compute, per-layer params (optimizer), per-layer
    collectives all scale with L; embed/head/loss are the intercept."""
    from repro.models import scanctl

    def one(cfg_l):
        with scanctl.cost_mode(True), _variant_ctx(variant):
            jitted, args = build_lowerable(cfg_l, shape, policy, variant)
            compiled = jitted.lower(*args).compile()
        cost = _cost_dict(compiled)
        colls = RL.collective_bytes_from_hlo(compiled.as_text())
        return {
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "colls": colls,
        }

    L = cfg.num_layers
    l1, l2 = _extrapolation_depths(cfg)
    if L <= l2:  # shallow enough to measure directly
        m = one(cfg.with_layers(L))
        m["depths"] = [L]
        return m
    m1, m2 = one(cfg.with_layers(l1)), one(cfg.with_layers(l2))

    def lerp(a, b):
        return a + (b - a) * (L - l1) / (l2 - l1)

    return {
        "flops": lerp(m1["flops"], m2["flops"]),
        "bytes": lerp(m1["bytes"], m2["bytes"]),
        "colls": {k: lerp(m1["colls"][k], m2["colls"][k])
                  for k in m1["colls"]},
        "depths": [l1, l2],
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             variant: str = "base", cache: bool = True) -> dict:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    cid = _cell_id(arch, shape_name, multi_pod, variant)
    cpath = os.path.join(RESULTS_DIR, cid + ".json")
    if cache and os.path.exists(cpath):
        with open(cpath) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        result = {"cell": cid, "status": "skipped", "reason": why}
        with open(cpath, "w") as f:
            json.dump(result, f)
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    policy = make_policy(mesh, variant)
    t0 = time.time()
    try:
        with _variant_ctx(variant):
            jitted, args = build_lowerable(cfg, shape, policy, variant)
            lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        cost = _cost_dict(compiled)
        try:
            mem = compiled.memory_analysis()
            mem_stats = {
                "peak_bytes": getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                - getattr(mem, "alias_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "generated_code_bytes": getattr(
                    mem, "generated_code_size_in_bytes", None),
            }
        except Exception:
            mem_stats = {}

        hlo = compiled.as_text()
        chips = mesh.devices.size
        # scan-raw numbers (while bodies counted once — kept for reference)
        raw_report = RL.build_report(arch, shape, describe(mesh), chips,
                                     {k: cost.get(k, 0.0) for k in
                                      ("flops", "bytes accessed")},
                                     hlo, cfg, mem_stats)
        # corrected costs: unrolled reduced-depth builds, extrapolated in L
        t0c = time.time()
        meas = measure_costs(cfg, shape, policy, variant)
        t_cost = time.time() - t0c
        report = RL.build_report(arch, shape, describe(mesh), chips,
                                 {"flops": meas["flops"],
                                  "bytes accessed": meas["bytes"]},
                                 hlo, cfg, mem_stats, colls=meas["colls"])
        result = {
            "cell": cid, "status": "ok",
            "t_lower_s": t_lower, "t_compile_s": t_compile,
            "t_costmeasure_s": t_cost,
            "mesh": describe(mesh),
            "memory": mem_stats,
            "cost": {k: float(v) for k, v in cost.items()
                     if isinstance(v, (int, float))},
            "cost_extrapolation_depths": meas.get("depths"),
            "roofline": report.to_dict(),
            "roofline_scanraw": raw_report.to_dict(),
        }
    except Exception as e:  # a failure here is a bug in the system
        result = {"cell": cid, "status": "error",
                  "error": f"{type(e).__name__}: {e}",
                  "trace": traceback.format_exc()[-2000:]}
    with open(cpath, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="base")
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args()

    if args.all:
        todo = [(a, s) for (a, s) in cells()]
    else:
        todo = [(args.arch, args.shape)]

    results = []
    for arch, shape in todo:
        r = run_cell(arch, shape, args.multi_pod, args.variant,
                     cache=not args.no_cache)
        status = r["status"]
        extra = ""
        if status == "ok":
            rl = r["roofline"]
            extra = (f" bottleneck={rl['bottleneck']}"
                     f" frac={rl['roofline_fraction']:.3f}"
                     f" mem/chip={(r['memory'].get('peak_bytes') or 0)/2**30:.2f}GiB"
                     f" compile={r['t_compile_s']:.0f}s")
        elif status == "error":
            extra = " " + r["error"][:160]
        print(f"[{status:>7}] {r['cell']}{extra}", flush=True)
        results.append(r)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    n_err = sum(1 for r in results if r["status"] == "error")
    print(f"done: {len(results)} cells, {n_err} errors")
    raise SystemExit(1 if n_err else 0)


if __name__ == "__main__":
    main()
