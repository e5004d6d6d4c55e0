#!/usr/bin/env python3
"""Split a cell's device-idle time by the engine phase the host was in.

    python3 bench/idle_split.py --workload ds67b-decode --seed 7 --seconds 51

One traced run of the cell, as ``bench/run.py --trace 1`` makes it but
without the check.  Prints one JSON line: the window, the device's busy
and idle seconds, the idle seconds under each innermost ``engine.*``
span (``engine.step`` is the step's own time between its phases;
``outside engine.step`` is the harness's loop between steps), the ten
longest idle gaps named by the engine span open at their middle
(``bench.window`` between steps), and the cell's per-layer metrics.
The parts of the split sum to the idle time.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as bench_run  # noqa: E402
from bench.lib import engine_spans, names, trace  # noqa: E402

OUTSIDE = "outside engine.step"


def split(tr: trace.Trace, eng: list, lo: float, hi: float) -> dict:
    """The idle split and the named gaps of one trace's window."""
    busy = trace.busy_ns(tr.ops, lo, hi, tr.n_devices)
    idle = engine_spans.idle_by_span(tr.ops, eng, lo, hi)
    idle[OUTSIDE] = idle.pop("outside", 0.0)
    window = [(lo, hi, "bench.window")]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / 1e9,
            "idle_s": (hi - lo - busy) / 1e9,
            "idle_share": 100.0 * (1.0 - busy / (hi - lo)),
            "idle_by_phase_s": dict(sorted(idle.items(),
                                           key=lambda kv: -kv[1])),
            "idle_gaps": trace.idle_gaps(tr.ops, eng + window, lo, hi),
            "n_steps": len(engine_spans.inside(eng, lo, hi, "engine.step"))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = names.cell_spec(args.workload)
    device = bench_run.device_info(spec["chips"])
    bench_run.enable_compile_cache()
    drv = names.driver(spec["config"]["engine"])
    kw = {"check": False} if spec["config"]["engine"] == "paged_lm" else {}
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        out = drv.run(spec, args.seed, args.seconds, trace_dir, T_PROCESS,
                      device, bench_run.log, **kw)
        run = out["run"]
        run.trace = trace.load(trace_dir)
        win = [s for s in run.trace.spans if s[2] == "bench.window"]
        run.traced_ns = lo, hi = win[0][0], win[0][1]
        line = {"workload": args.workload, "seed": args.seed}
        line.update(split(run.trace, engine_spans.of_run(run), lo, hi))
        line["metrics"] = {}
        for m in spec["per_layer"]:
            v = names.metric_reader(m["name"]).read(run)
            if v is not None:
                line["metrics"][m["name"]] = v
        line["end_to_end"] = out["end_to_end"]
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
