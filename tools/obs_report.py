#!/usr/bin/env python
"""Render an observability snapshot JSON as a terminal report.

    PYTHONPATH=src python tools/obs_report.py snap.json [--requests N]

The input is the dict :meth:`repro.obs.Observability.snapshot` produces
(saved with ``json.dump(obs.snapshot(), f)``): metric families under
``metrics``, per-request span summaries under ``requests``.  The report
shows non-zero counters and gauges, histogram p50/p99/mean via the same
shared quantile implementation the registry uses
(:func:`repro.obs.quantile_from_counts`), the energy split by
component, and the top-energy request spans.

Everything here is read-side formatting over the snapshot dict; the
numbers are computed by the obs layer, not re-derived.
"""
from __future__ import annotations

import argparse
import json
import sys


def _fmt_si(v: float) -> str:
    """Engineering-format a non-negative number (1.23e6 -> '1.23M')."""
    for cut, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "k")):
        if abs(v) >= cut:
            return f"{v / cut:.2f}{suffix}"
    if v and abs(v) < 0.1:
        for cut, suffix in ((1e-3, "m"), (1e-6, "u"), (1e-9, "n"),
                            (1e-12, "p")):
            if abs(v) >= cut:
                return f"{v / cut:.2f}{suffix}"
    return f"{v:g}"


def _series_rows(family: dict):
    """(name, label-string, value) rows, non-zero series only."""
    for name in sorted(family):
        for label, value in sorted(family[name]["series"].items()):
            if value:
                yield name, label, value


def _hist_stats(hist: dict):
    """(label, count, mean, p50, p99) per series of one histogram."""
    from repro.obs import quantile_from_counts

    bounds = hist["buckets"]
    for label, s in sorted(hist["series"].items()):
        if not s["count"]:
            continue
        mean = s["sum"] / s["count"]
        p50 = quantile_from_counts(s["counts"], bounds, 0.5,
                                   s["min"], s["max"])
        p99 = quantile_from_counts(s["counts"], bounds, 0.99,
                                   s["min"], s["max"])
        yield label, s["count"], mean, p50, p99


def render(snap: dict, n_requests: int = 8) -> str:
    lines = []
    metrics = snap.get("metrics", {})

    lines.append("== counters ==")
    for name, label, value in _series_rows(metrics.get("counters", {})):
        tag = f"{name}{{{label}}}" if label else name
        lines.append(f"  {tag:44s} {_fmt_si(value):>10s}")

    lines.append("== gauges ==")
    for name, label, value in _series_rows(metrics.get("gauges", {})):
        tag = f"{name}{{{label}}}" if label else name
        lines.append(f"  {tag:44s} {value:10.3f}")

    lines.append("== histograms (count / mean / p50 / p99) ==")
    for name in sorted(metrics.get("histograms", {})):
        hist = metrics["histograms"][name]
        for label, count, mean, p50, p99 in _hist_stats(hist):
            tag = f"{name}{{{label}}}" if label else name
            lines.append(f"  {tag:34s} {count:6d} {mean:9.2f} "
                         f"{p50:9.2f} {p99:9.2f}")

    reqs = snap.get("requests", [])
    if reqs:
        total_j = sum(r["joules"] for r in reqs)
        by_status: dict = {}
        for r in reqs:
            by_status[r["status"]] = by_status.get(r["status"], 0) + 1
        status_s = " ".join(f"{k}={v}" for k, v in sorted(by_status.items()))
        lines.append(f"== requests: {len(reqs)} ({status_s}), "
                     f"total {_fmt_si(total_j)}J ==")
        lines.append(f"  {'uid':>5s} {'status':8s} {'tok':>4s} "
                     f"{'steps':>5s} {'ttft':>6s} {'itl':>6s} "
                     f"{'joules':>9s} {'share':>6s}")
        top = sorted(reqs, key=lambda r: -r["joules"])[:n_requests]
        for r in top:
            itl = f"{r['itl']:6.2f}" if r.get("itl") is not None else "     -"
            ttft = (f"{r['ttft']:6.1f}" if r.get("ttft") is not None
                    else "     -")
            share = r["joules"] / total_j if total_j else 0.0
            lines.append(f"  {r['uid']:5d} {r['status']:8s} "
                         f"{r['tokens']:4d} {r['decode_steps']:5d} "
                         f"{ttft} {itl} {_fmt_si(r['joules']):>9s} "
                         f"{share * 100:5.1f}%")
        if len(reqs) > n_requests:
            lines.append(f"  ... {len(reqs) - n_requests} more "
                         f"(--requests N to widen)")

    if snap.get("dropped_events"):
        lines.append(f"!! {snap['dropped_events']} events dropped "
                     f"(raise max_events)")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="render an Observability.snapshot() JSON")
    ap.add_argument("snapshot", help="snapshot JSON path ('-' for stdin)")
    ap.add_argument("--requests", type=int, default=8, metavar="N",
                    help="show the N highest-energy request spans")
    args = ap.parse_args(argv)
    if args.snapshot == "-":
        snap = json.load(sys.stdin)
    else:
        with open(args.snapshot) as f:
            snap = json.load(f)
    sys.stdout.write(render(snap, n_requests=args.requests))
    return 0


if __name__ == "__main__":
    sys.exit(main())
