"""Read the numbers a cell's limits are set from, over many seeds, in one
process on the chip.

    python3 bench/calibrate.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds <k>]

Each seed is one run of the cell (``harness.run_cell``: set-up, a window of
``--seconds``, the check). It prints, a line a seed, the program's widest
logit gap and differing transfer words and, on the first ``--control-seeds``
seeds, the widest gap of the float8 control at the same prompts and served
tokens. The lower
reading of a limit is the largest the program gives over a dozen seeds or
more; the upper the smallest the control gives. Benchmark runs never run
the control.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from bench.harness import run_cell
    from bench.run import CACHE_DIR
    from bench.spec import load_cell

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cell = load_cell(args.workload, ROOT)
    rows = []
    for n, seed in enumerate(args.seeds):
        modes = ("f32", "fp8") if n < args.control_seeds else ("f32",)
        t = time.perf_counter()
        res = run_cell(cell, seed, args.seconds, False, t_start=t,
                       modes=modes, log=lambda msg: None)
        row = {"seed": seed, "correct": res["correct"],
               "logit_gap": res["checks"]["logit_gap"]["value"],
               "transfer_bits": res["checks"]["transfer_bits"]["value"],
               "control_gap_fp8": res["diagnostics"].get("control_gap_fp8"),
               "attempted": res["attempted"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               "stage_ms": res["diagnostics"]["stage_ms"],
               "run_s": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
    gaps = [r["logit_gap"] for r in rows]
    summary = {"workload": args.workload, "seeds": len(rows),
               "program_max": max(gaps), "program_min": min(gaps)}
    ctl = [r["control_gap_fp8"] for r in rows
           if r["control_gap_fp8"] is not None]
    if ctl:
        summary.update(control_min=min(ctl), control_max=max(ctl))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
