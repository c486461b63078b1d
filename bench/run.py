"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout holding ``BENCHMARK.json``, ``bench/`` and the
program under ``src/``. The cell (``workloads`` in ``BENCHMARK.json``) names
a configuration and a traffic mix; their files, the cell's limits and the
per-layer metrics' readers are found by name under ``bench/``.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` profiles
the first batches of the window and prints its per-layer metrics, with the
device's busy and traced seconds and a breakdown of the trace. Either way
the served tokens are checked against the plain reference once the window
has closed, and the numbers compared are printed beside their limits as the
last lines of stderr and under ``checks``, the last key of the result.

The last line of stdout is the result, one JSON object. Without a TPU, or
with fewer chips than the cell asks for, the run exits 2 and prints none.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
# a fixed directory inside the checkout: its path is part of the cache key
CACHE_DIR = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.spec import load_cell
    cell = load_cell(args.workload, ROOT)

    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"no TPU for this cell: {len(devs)} {devs[0].platform} "
              f"device(s), the cell asks for {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2

    from bench.harness import run_cell
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
