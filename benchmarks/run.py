"""Benchmark harness: one module per paper table/figure.

``python -m benchmarks.run [--only table2,fig3]`` prints CSV lines
``table,row,key=value,...`` and writes benchmarks/results/benchmarks.json.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import time
import traceback

MODULES = [
    "table1_exponent_stats",
    "table2_codec_throughput",
    "table3_topk_ablation",
    "table4_cross_dataset",
    "table5_granularity",
    "table6_escape_metadata",
    "table7_precalibration",
    "table8_fp8",
    "table9_lossless_check",
    "fig2_e2e_serving",
    "fig3_transfer_sweeps",
    "fig4_breakdown",
    "fig5_layerwise",
    "fig6_resident_capacity",
    "appendix_a_hiding",
    # needs 8 host devices: run as its own process (CI --only xpod_chunked);
    # skips gracefully inside a full in-process sweep
    "xpod_chunked_smoke",
    # bulk-data plane: checkpoint round-trip + 2-pod ring_reduce
    # (CI --only bulkplane; the ring leg skips below 2 host devices)
    "bulkplane_smoke",
]

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "results",
                            "benchmarks.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated module substrings to run")
    args = ap.parse_args()
    only = [s for s in args.only.split(",") if s]
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    rows = []

    def emit(table: str, row: str, values: dict) -> None:
        rows.append({"table": table, "row": row, **values})
        kv = ",".join(f"{k}={v}" for k, v in values.items())
        print(f"{table},{row},{kv}", flush=True)

    failures = 0
    for name in MODULES:
        if only and not any(s in name for s in only):
            continue
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            mod.run(emit)
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {name} FAILED:\n{traceback.format_exc()}", flush=True)

    os.makedirs(os.path.dirname(RESULTS_PATH), exist_ok=True)
    with open(RESULTS_PATH, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"# wrote {len(rows)} rows -> {RESULTS_PATH}; {failures} module failures")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
