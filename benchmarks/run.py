"""Benchmark aggregator — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--skip-kernels] [--json PATH]

The tensor-parallel kernel rows need four devices in this process (on
the CPU: ``XLA_FLAGS=--xla_force_host_platform_device_count=4``, as
``make bench`` sets).

Prints ``name,us_per_call,derived`` CSV rows (derived holds the
claim-relevant numbers, ours vs the paper's) and **merges** the rows into
``BENCH_kernels.json`` (name -> µs + metadata) so the perf trajectory is
machine-readable across PRs instead of only printed.  Stale-row pruning
is scoped to the row families a run actually measured: a
``--skip-kernels`` smoke run (``make verify``) updates and prunes the
simulator/serving rows without touching the kernel/resilience rows,
while a full run (no flag) prunes renamed/deleted benches everywhere.
"""
from __future__ import annotations

import argparse
import time


def bench_explore_graph_cache():
    """Workload-graph memoization win for the Table IV exploration sweep."""
    from repro.core import explore

    explore.clear_graph_cache()
    t0 = time.perf_counter()
    explore.run_exploration(quadrature=4)
    cold = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    explore.run_exploration(quadrature=4)
    warm = (time.perf_counter() - t0) * 1e6
    info = explore._decode_graph.cache_info()
    return [("explore_sweep_cold", cold,
             f"graph cache cold; decode graphs built {info.misses}x"),
            ("explore_sweep_warm", warm,
             f"graph cache warm; speedup={cold/warm:.2f}x "
             f"(hits={info.hits})")]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-kernels", action="store_true",
                    help="skip interpret-mode kernel microbenches (slow)")
    ap.add_argument("--json", default=None,
                    help="output path for BENCH_kernels.json "
                         "(default: ./BENCH_kernels.json)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks.bench_kernels import BENCH_JSON, write_bench_json
    from benchmarks.paper_tables import ALL_BENCHES

    print("name,us_per_call,derived")
    rows = []
    for bench in ALL_BENCHES:
        rows.extend(bench())
    rows.extend(bench_explore_graph_cache())
    # serving traffic harness: smoke N always (so the serving_* rows
    # survive the full-run prune and verify exercises the engine loop),
    # thousand-request sweep on full runs
    from benchmarks.bench_serving import bench_serving
    rows.extend(bench_serving(full=not args.skip_kernels))
    if not args.skip_kernels:
        from benchmarks.bench_kernels import bench_kernels
        rows.extend(bench_kernels())
        from benchmarks.bench_resilience import bench_resilience
        rows.extend(bench_resilience())
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    out_path = args.json or BENCH_JSON
    # prune stale (renamed/deleted) rows only within the row families
    # this run actually measured: simulator + serving rows always run;
    # kernel/resilience rows only without --skip-kernels, and their
    # stale entries must survive a smoke run untouched
    ran = {"simulator", "serving"}
    if not args.skip_kernels:
        ran |= {"kernels", "resilience"}
    write_bench_json(rows, out_path, ran_suites=ran)
    print(f"# wrote {out_path}")


if __name__ == "__main__":
    main()
