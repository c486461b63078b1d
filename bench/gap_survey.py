"""Every position's logit gap in a cell, for the program, the float8 control
and faults planted in the program's expert layer, over many seeds, in one
process on the chip.

    python3 bench/gap_survey.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds <k>] [--faults <name> ...]

Each seed is one run of the cell (``harness.run_cell``, as in
``bench/calibrate.py``). ``bench/check.py`` reduces the per-position gaps of
the sampled requests to their widest; here they are kept whole, and each run
prints three numbers of them: ``widest`` (what ``correct`` reads), ``mean``
and ``disagree``, the share of positions whose served token is not the
reference's best. On the first ``--control-seeds`` seeds the same three are
printed for the float8 control. Each of ``--faults`` (names in ``FAULTS``) is
planted for a run of every seed, after the sound runs, and taken out again.
The last line is a summary: each number's least and largest per variant.
Benchmark runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def expert_dropped(mp):
    """The first held expert's rows never reach the output."""
    import jax.numpy as jnp
    from repro.models import moe
    real = moe.moe_grouped_matmul

    def dropped(lhs, rhs, sizes, *args, **kw):
        out = real(lhs, rhs, sizes, *args, **kw)
        return jnp.where(jnp.arange(out.shape[0])[:, None] < sizes[0], 0,
                         out)
    mp.setattr(moe, "moe_grouped_matmul", dropped)


def groups_not_limited(mp):
    """Experts chosen over all groups, not inside the best ones."""
    from repro.models import moe
    real = moe.route
    mp.setattr(moe, "route", lambda p, x, cfg: real(
        p, x, dataclasses.replace(cfg, n_group=1, topk_group=1)))


# faults in the expert layer; each takes a ``pytest.MonkeyPatch``
FAULTS = {"expert-dropped": expert_dropped,
          "groups-not-limited": groups_not_limited}


def summary(gaps):
    """The three numbers of one run's (rows, positions) gaps."""
    import numpy as np
    g = np.asarray(gaps, np.float64)
    return {"widest": float(g.max()), "mean": float(g.mean()),
            "disagree": float((g > 0).mean()), "positions": int(g.size)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=0)
    ap.add_argument("--faults", nargs="*", default=[], choices=sorted(FAULTS))
    args = ap.parse_args(argv)

    import jax
    import pytest
    from bench import check
    from bench.harness import run_cell
    from bench.run import CACHE_DIR
    from bench.spec import load_cell

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cell = load_cell(args.workload, ROOT)
    kept = []
    real_gaps = check.gaps

    def keeping(logits, served):
        g = real_gaps(logits, served)
        kept.append(g)
        return g

    rows = []
    for variant in ["program"] + list(args.faults):
        mp = pytest.MonkeyPatch()
        mp.setattr(check, "gaps", keeping)   # control_gaps calls it too
        if variant != "program":
            FAULTS[variant](mp)
        try:
            for n, seed in enumerate(args.seeds):
                control = variant == "program" and n < args.control_seeds
                kept.clear()
                t = time.perf_counter()
                res = run_cell(cell, seed, args.seconds, False, t_start=t,
                               modes=("f32", "fp8") if control else ("f32",),
                               log=lambda msg: None)
                row = {"variant": variant, "seed": seed,
                       "correct": res["correct"], **summary(kept[0]),
                       "control": summary(kept[1]) if control else None,
                       "run_s": time.perf_counter() - t}
                rows.append(row)
                print(json.dumps(row), flush=True)
        finally:
            mp.undo()

    out = {}
    for r in rows:
        for name, nums in ((r["variant"], r), ("control", r["control"])):
            if nums is None:
                continue
            s = out.setdefault(name, {"runs": 0})
            s["runs"] += 1
            for k in ("widest", "mean", "disagree"):
                lo, hi = s.get(k, (nums[k], nums[k]))
                s[k] = (min(lo, nums[k]), max(hi, nums[k]))
    print(json.dumps({"workload": args.workload, "summary": out}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
