#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, in one process.

    python3 bench/calibrate.py --workload <name> --seconds <s> \
        --seeds 1 2 3 ... [--control-seeds 1 2 3]

For each seed it runs the cell once (set-up, window, check) and prints
one JSON line with the number the check compares; for each control seed
it also prints the control's reading: the same reference computed with
int4 weights in place of the program.  The limit lies between the
largest program reading and the smallest control reading.  Needs the
chips the cell asks for; the benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as bench_run  # noqa: E402
from bench.lib import names  # noqa: E402

CONTROL_BITS = 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    spec = names.cell_spec(args.workload)
    device = bench_run.device_info(spec["chips"])
    bench_run.enable_compile_cache()
    drv = names.driver(spec["config"]["engine"])
    for seed in args.seeds:
        t = time.perf_counter()
        out = drv.run(spec, seed, args.seconds, None, t, device,
                      bench_run.log,
                      control_bits=(CONTROL_BITS if seed in args.control_seeds
                                    else None))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "end_to_end": out["end_to_end"],
                          "memory_peak_bytes": out["memory_peak_bytes"],
                          "checks": out["checks"]}), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
