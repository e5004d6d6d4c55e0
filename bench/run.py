#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics; ``--trace 1``
records a profiler trace of the whole window and reports the cell's
per-layer metrics from it.  Either way the run ends with the check that
decides ``correct``: what the window served, against the configuration's
plain reference.  The last line of stdout is one JSON object; the numbers
compared, each beside its limit, are the last lines of stderr and the
``checks`` key that ends that object.

It exits non-zero, and prints no result, on a host whose JAX finds no
TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import names  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_info(chips: int) -> dict:
    """The devices JAX found; raises SystemExit without enough TPUs."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform "
                         f"{info['platform']!r}); nothing was measured")
    if info["count"] < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{info['count']}")
    return info


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_process: float, device: dict, hooks=None) -> dict:
    """One run of a cell; returns the result line as a dict."""
    from bench.lib import trace as tr
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        drv = names.driver(spec["config"]["engine"])
        out = drv.run(spec, seed, seconds, trace_dir, t_process, device,
                      log, hooks=hooks)
        run = out["run"]
        metrics = {}
        dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
        line = {}
        if trace:
            run.trace = tr.load(trace_dir)
            win = [s for s in run.trace.spans if s[2] == "bench.window"]
            run.traced_ns = (win[0][0], win[0][1])
            lo, hi = run.traced_ns
            dev["busy_s"] = tr.busy_ns(run.trace.ops, lo, hi,
                                       run.trace.n_devices) / 1e9
            dev["window_s"] = (hi - lo) / 1e9
            for m in spec["per_layer"]:
                v = names.metric_reader(m["name"]).read(run)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            line["breakdown"] = {
                "device_ops": tr.top_ops(run.trace.ops, lo, hi),
                "idle_gaps": tr.idle_gaps(run.trace.ops, run.trace.spans,
                                          lo, hi)}
        else:
            for m in spec["end_to_end"]:
                metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    checks = dict(out["checks"],
                  failed_requests={"value": out["failed"], "limit": 0})
    correct = (out["attempted"] > 0
               and all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                       for c in checks.values()))
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": dev}
    result.update(line)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    spec = names.cell_spec(args.workload)
    device = device_info(spec["chips"])
    log(f"{args.workload} seed {args.seed}: {device['kind']} "
        f"x{device['count']}; compile cache {enable_compile_cache()}")
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      T_PROCESS, device)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
