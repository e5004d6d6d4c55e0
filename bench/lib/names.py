"""Find every piece of a cell by its name in ``BENCHMARK.json``.

A cell ``<w>`` names a configuration ``<c>`` and a traffic mix ``<t>``:

* ``bench/workloads/<w>.json``  engine sizes and the correctness check
* the configuration's ``file`` (``bench/configs/<c>.json``), whose
  ``family`` picks ``bench/reference/<family>.py`` and
  ``bench/ops/<family>.py`` and whose ``engine`` picks
  ``bench/drivers/<engine>.py``
* ``bench/traffic/<t>.json``     the mix, read by ``lib/traffic.py``
* per-layer metric ``<m>[.<suffix>]`` -> ``bench/metrics/<m>.py``

Adding a cell, a configuration or a metric adds files and entries; no
file here changes.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"bench: no file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def workload_entry(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"bench: no workload {name!r} in BENCHMARK.json")


def config_entry(bm: dict, name: str) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"bench: no config {name!r} in BENCHMARK.json")


def metric_module_name(metric: str) -> str:
    """``gemm_roofline.chat`` -> ``gemm_roofline``: one reader per
    quantity, shared by the cells that report it."""
    return metric.split(".", 1)[0]


def metric_reader(metric: str):
    return importlib.import_module(
        f"bench.metrics.{metric_module_name(metric)}")


def driver(engine: str):
    return importlib.import_module(f"bench.drivers.{engine}")


def reference(family: str):
    return importlib.import_module(f"bench.reference.{family}")


def ops(family: str):
    return importlib.import_module(f"bench.ops.{family}")


def cell_spec(name: str, bm: dict | None = None) -> dict:
    """Everything one run of cell ``name`` needs, merged from its files."""
    bm = benchmark() if bm is None else bm
    entry = workload_entry(bm, name)
    centry = config_entry(bm, entry["config"])
    spec = {
        "name": name,
        "chips": entry["chips"],
        "config": _json(ROOT / centry["file"]),
        "traffic": _json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        "workload": _json(BENCH / "workloads" / f"{name}.json"),
        "end_to_end": [m for m in bm["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bm["per_layer"]
                      if name in m.get("workloads", [name])],
    }
    return spec


def peaks(device_kind: str) -> dict:
    table = _json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"bench: device kind {device_kind!r} is not in "
                       f"bench/peaks.json; add its published peaks")
    return table["devices"][device_kind]
