"""Trace one batch of a cell on the chip and keep what a reader needs.

    python3 bench/trim_trace.py --workload <name> --seed <n> --out <dir>

Writes to ``<dir>``: ``summary.json`` (every plane and line of the trace
with its busiest event names, to read the trace by hand), ``reduced.json``
(busy and window seconds, top device operations, longest idle gaps, per
harness span), and ``trimmed.textproto``, the device operations and the
harness's host thread inside one ``bench.transfer`` span, as an XSpace text
proto small enough to keep as a test of the reduction
(``bench/tests/data/``). Needs the chip, like ``run.py``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def textproto(data, lo: float, hi: float) -> str:
    """Device ops and the harness thread's host events inside [lo, hi]."""
    from bench import trace
    out = []
    pid = 0
    for plane in data.planes:
        is_dev = bool(trace.DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            if is_dev and line.name != trace.OPS_LINE:
                continue
            if not is_dev and not any(n.startswith(trace.SPAN_PREFIX)
                                      for n, _, _ in evs):
                continue
            keep = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                    if e > lo and s < hi]
            if keep:
                lines.append((line.name, keep))
        if not lines:
            continue
        pid += 1
        names = sorted({n for _, evs in lines for n, _, _ in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = [f'planes {{ id: {pid} name: {json.dumps(plane.name)}']
        for li, (lname, evs) in enumerate(lines):
            t0 = int(min(s for _, s, _ in evs))
            body.append(f'  lines {{ id: {li + 1} name: {json.dumps(lname)} '
                        f'timestamp_ns: {t0}')
            for n, s, e in evs:
                body.append(f'    events {{ metadata_id: {ids[n]} offset_ps: '
                            f'{int(round((s - t0) * 1000))} duration_ps: '
                            f'{int(round((e - s) * 1000))} }}')
            body.append('  }')
        for n, i in ids.items():
            body.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: {json.dumps(n)} }} }}')
        body.append('}')
        out.extend(body)
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    from bench import harness, program, trace, traffic
    from bench.run import CACHE_DIR
    from bench.spec import load_cell
    from jax.profiler import ProfileData

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cell = load_cell(args.workload, ROOT)
    served = program.Served(cell.config, cell.traffic, args.seed)
    gen = traffic.ClosedLoop(cell.traffic, cell.config["vocab_size"],
                             args.seed)
    harness.serve_batch(served, gen, 0, keep=False)
    print(f"set-up {time.perf_counter() - T_START:.3f} s", flush=True)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=trace.options())
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            b, _ = harness.serve_batch(served, gen, 1, keep=False)
        jax.profiler.stop_trace()
        path = trace.find_xplane(d)
        size = Path(path).stat().st_size
        data = ProfileData.from_file(path)
    print(f"xplane {size} bytes; stages {b.times}", flush=True)
    red = trace.reduce(data)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "summary.json").write_text(json.dumps(trace.summary(data),
                                                 indent=1))
    spans = {}
    for n, s, e in red.spans:
        spans.setdefault(n, []).append((e - s) * 1e-9)
    (out / "reduced.json").write_text(json.dumps({
        "xplane_bytes": size, "host_times": b.times,
        "busy_s": red.busy_s, "window_s": red.window_s,
        "busy_in": {n: red.busy_in([n]) for n in harness.STAGES},
        "spans": spans, "top_ops": red.top_ops(40),
        "idle_gaps": red.idle_gaps(20)}, indent=1))
    lo, hi = next((s, e) for n, s, e in red.spans
                  if n == "bench.transfer")
    (out / "trimmed.textproto").write_text(textproto(data, lo, hi))
    print(json.dumps({"busy_s": red.busy_s, "window_s": red.window_s,
                      "top_ops": red.top_ops(12),
                      "idle_gaps": red.idle_gaps(8)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
