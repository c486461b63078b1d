"""One run of one cell: set-up, the measured window, the check, the result.

The loop is closed over the engine's own stages. Batch ``i`` (from 1; batch
0 is the warm-up) is dispatched when batch ``i - 1`` has finished decoding;
its requests are due at dispatch. Each stage is ended by
``jax.block_until_ready`` and timed on the host clock:

    prefill   ``engine.prefill``            (span ``bench.prefill``)
    transfer  ``engine.transfer``           (span ``bench.transfer``; with a
              compressed-resident decode side it admits into the pool)
    decode    ``engine.decode``, new_tokens greedy steps (span
              ``bench.decode``)

A request's time to first token runs from its batch's dispatch to the end of
the transfer: the decode side then holds its cache and its first token.
Batches dispatched inside ``--seconds`` run to completion, and the window
ends when the last of them does.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Dict, List

import jax
import numpy as np

from bench import check, counts, program, trace, traffic
from bench.reference import common as ref_common
from bench.spec import Cell

STAGES = ("prefill", "transfer", "decode")


@dataclasses.dataclass
class Batch:
    index: int
    times: Dict[str, float]          # stage -> seconds
    tokens: object                   # (B, 1 + new_tokens) device array
    traced: bool = False

    @property
    def ttft_s(self) -> float:
        return self.times["prefill"] + self.times["transfer"]


_COMPILES = [0]                  # XLA compilations seen in this process


def _count_compiles(event, duration_secs, **_):
    if event.endswith("backend_compile_duration"):
        _COMPILES[0] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compiles)


def serve_batch(served, gen, index: int, keep: bool):
    """Run one batch through the three stages; returns the Batch and, when
    ``keep``, the (sent, received) caches for the transfer check."""
    times = {}
    batch = gen.prompts(index)
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.prefill"):
        pre = jax.block_until_ready(served.prefill(batch))
    times["prefill"] = time.perf_counter() - t
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.transfer"):
        got = jax.block_until_ready(served.transfer(pre.state))
    times["transfer"] = time.perf_counter() - t
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.decode"):
        toks = jax.block_until_ready(served.decode(pre.first_token, got))
    times["decode"] = time.perf_counter() - t
    served_tokens = jax.numpy.concatenate([pre.first_token[:, None], toks],
                                          axis=1)
    kept = (pre.state.cache, got) if keep else None
    return Batch(index, times, served_tokens), kept


def percentile(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q))


def end_to_end(batches: List[Batch], gen, window_s: float, setup_s: float
               ) -> Dict[str, float]:
    ttft = [b.ttft_s * 1e3 for b in batches for _ in range(gen.batch)]
    decode_s = sum(b.times["decode"] for b in batches)
    steps = gen.new_tokens * len(batches)
    tokens = len(batches) * gen.batch * (1 + gen.new_tokens)
    tpot_ms, tokens_per_s = decode_s / steps * 1e3, tokens / window_s
    # the resident cells' decode is a host loop, noisier than the raw
    # decode: the same quantities under names of their own, so that
    # their wider bounds do not blind the other cells
    return {
        "ttft_p50_ms": percentile(ttft, 50),
        "ttft_p90_ms": percentile(ttft, 90),
        "tpot_ms": tpot_ms,
        "output_tokens_per_s": tokens_per_s,
        "resident_tpot_ms": tpot_ms,
        "resident_output_tokens_per_s": tokens_per_s,
        "setup_s": setup_s,
    }


def reference_check(cell: Cell, seed: int, gen, dispatches: List[int],
                    served_rows, requests: List[int], modes=("f32",)):
    """Run the reference over the sampled requests. Request r is row
    r % batch of dispatch ``dispatches[r // batch]``; ``served_rows[r]`` is
    its served tokens (1 + new_tokens). Returns the widest gap per mode (the
    control's gap for modes other than f32)."""
    conf = cell.config
    fam = cell.reference()
    s, n = gen.prompt_tokens, gen.new_tokens
    rows = []
    for r in requests:
        b, j = divmod(r, gen.batch)
        prompt = gen.prompt_rows(dispatches[b])[j]
        rows.append(np.concatenate([prompt, served_rows[r][:n]]))
    served = np.stack([served_rows[r] for r in requests])
    with jax.default_matmul_precision("highest"):
        logits = ref_common.logits_at(fam, conf, seed, np.stack(rows),
                                      first=s - 1, count=n + 1, modes=modes)
    out = {"logit_gap": float(check.gaps(logits["f32"], served).max())}
    for m in modes:
        if m != "f32":
            out[f"control_gap_{m}"] = float(
                check.control_gaps(logits["f32"], logits[m]).max())
    return out


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, modes=("f32",), peak=None, log=None) -> dict:
    """One run; returns the result dict the CLI prints (``checks`` last).

    ``peak`` stands in for the device's row of the peaks table where the
    device has none (a CPU test); ``modes`` adds the control's readings."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    conf, mix, limits = cell.config, cell.traffic, cell.limits
    dev = jax.devices()[0]
    peak = peak or counts.peaks(dev.device_kind)

    served = program.Served(conf, mix, seed, cell.root)
    gen = traffic.ClosedLoop(mix, conf["vocab_size"], seed)
    log(f"cell {cell.name}: backend {served.backend}, max_seq "
        f"{served.max_seq}, {gen.batch} x {gen.prompt_tokens} prompt tokens, "
        f"{gen.new_tokens} new")
    warm, _ = serve_batch(served, gen, 0, keep=False)
    del warm
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")

    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 5])
    n_trace = int(mix["trace_batches"]) if traced else 0
    batches: List[Batch] = []
    kept = None
    reduced = None
    before = _COMPILES[0]
    logdir = tempfile.TemporaryDirectory() if traced else None
    t0 = time.perf_counter()
    while not batches or time.perf_counter() - t0 < seconds:
        i = len(batches) + 1
        if i == 1 and n_trace:
            jax.profiler.start_trace(logdir.name,
                                     profiler_options=trace.options())
        keep = rng.random() < 1.0 / i                 # reservoir of one
        with jax.profiler.TraceAnnotation("bench.window"):
            b, pair = serve_batch(served, gen, i, keep)
        b.traced = i <= n_trace
        if i == n_trace:
            jax.profiler.stop_trace()
        batches.append(b)
        if keep:
            kept = pair
    window_s = time.perf_counter() - t0
    in_window = _COMPILES[0] - before
    attempted = len(batches) * gen.batch
    log(f"window {window_s:.3f} s: {len(batches)} batches, {attempted} "
        f"requests, {in_window} compilations inside")

    memory_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    counters = served.counters()
    chunk, cap = served.engine.tc.chunk, served.engine.tc.cap
    geom = getattr(kept[1], "geom", None) if kept else None
    cache_elems = [int(np.prod(x.shape)) for x in jax.tree.leaves(kept[0])]
    bits = check.differing_words(kept[0], served.received_cache(kept[1]))
    served_rows = [row for b in batches for row in np.asarray(b.tokens)]
    del kept, served
    for b in batches:
        b.tokens = None
    gc.collect()

    if traced:
        reduced = trace.load(trace.find_xplane(logdir.name))
        logdir.cleanup()

    requests = check.sample_requests(seed, attempted,
                                     int(mix["check_requests"]))
    t_ref = time.perf_counter()
    readings = reference_check(cell, seed, gen, [b.index for b in batches],
                               served_rows, requests, modes)
    log(f"reference over {len(requests)} requests: "
        f"{time.perf_counter() - t_ref:.3f} s")
    controls = {k: v for k, v in readings.items() if k.startswith("control")}
    checks = check.verdict({"logit_gap": readings["logit_gap"],
                            "transfer_bits": float(bits)}, limits)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": check.all_ok(checks), "attempted": attempted,
              "failed": 0}
    if traced:
        ctx = SimpleNamespace(
            trace=reduced, batches=batches, conf=conf, mix=mix, gen=gen,
            fam=cell.reference(), peak=peak, counters=counters,
            cache_elems=cache_elems, geom=geom, chunk=chunk, cap=cap)
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.idle_gaps(10)}
    else:
        values = end_to_end(batches, gen, window_s, setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["diagnostics"] = {"window_s": window_s, "batches": len(batches),
                             "compiles_in_window": in_window,
                             "stage_ms": {st: [b.times[st] * 1e3
                                               for b in batches]
                                          for st in STAGES},
                             "counters": counters, **controls}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for line in check.lines(checks):
        log(line)
    return result
