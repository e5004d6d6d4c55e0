#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest arrival rate at which the
queue does not grow through the window.

    python3 bench/sweep.py --workload ds67b-chat --seconds 40 \
        --rates 1.6 2.0 2.4 --seeds 5 6

One process, one run per seed and rate (set-up included, no check).
Each prints a JSON line with the queue length at the window's open and close, the
requests completed and the end-to-end metrics.  A cell's rate is fixed
from this sweep once, in its traffic file; the benchmark never searches.
"""
from __future__ import annotations

import argparse
import copy
import gc
import itertools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as bench_run  # noqa: E402
from bench.lib import names  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[5])
    args = ap.parse_args(argv)
    base = names.cell_spec(args.workload)
    device = bench_run.device_info(base["chips"])
    bench_run.enable_compile_cache()
    drv = names.driver(base["config"]["engine"])
    for seed, rate in itertools.product(args.seeds, args.rates):
        spec = copy.deepcopy(base)
        spec["traffic"]["rate_per_s"] = rate
        t = time.perf_counter()
        out = drv.run(spec, seed, args.seconds, None, t, device,
                      bench_run.log, check=False)
        done = sum(1 for r in out["run"].requests
                   if r["req"].status.value == "ok")
        print(json.dumps({"seed": seed, "rate_per_s": rate,
                          "queue": out["queue"],
                          "judged": out["attempted"], "completed": done,
                          "failed": out["failed"],
                          "end_to_end": out["end_to_end"]}), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
