"""``BENCHMARK.json`` against the harness: every name resolves to its
files, every per-layer metric's ``moves`` is reported where it is read,
the harness refuses the CPU, and traffic is a pure function of the seed.
"""
import ast
import json
import os
import re
import subprocess
import sys

import pytest

from bench.lib import names, traffic

BM = names.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"] and BM["command"][1] == "bench/run.py"
    assert 1 <= BM["run_seconds"] <= 51
    all_names = ([c["name"] for c in BM["configs"]] + CELLS
                 + [m["name"] for m in BM["end_to_end"] + BM["per_layer"]])
    assert len(all_names) == len(set(all_names))
    assert all(NAME.match(n) for n in all_names)
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert len(json.dumps(BM)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    spec = names.cell_spec(cell)
    cfg = spec["config"]
    assert names.driver(cfg["engine"]).run
    assert names.reference(cfg["family"])
    assert names.ops(cfg["family"])
    if cfg["engine"] == "paged_lm":
        assert spec["traffic"]["loop"] in ("open", "closed")
    else:
        assert "loop" not in spec["traffic"] and spec["traffic"]["num_steps"]
    assert spec["workload"]["check"]["limit"] > 0
    w = names.workload_entry(BM, cell)
    assert w["chips"] == 1 and len(w["why"]) <= 200
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer metric
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and spec["per_layer"]


@pytest.mark.parametrize("metric", [m["name"] for m in BM["per_layer"]])
def test_per_layer_metric_has_reader_and_moves_where_listed(metric):
    m = next(x for x in BM["per_layer"] if x["name"] == metric)
    assert callable(names.metric_reader(metric).read)
    moved = next(x for x in BM["end_to_end"] if x["name"] == m["moves"])
    for cell in m["workloads"]:
        assert cell in CELLS
        assert cell in moved.get("workloads", CELLS), (metric, cell)


def test_configs_state_their_cut():
    for c in BM["configs"]:
        data = json.loads((names.ROOT / c["file"]).read_text())
        assert c["file"].startswith("bench/configs/")
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        assert c["source"] == data["source"]
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size")), key


def test_new_pieces_resolve_from_names_alone():
    """A later cell or metric adds files and entries: the harness finds
    a new metric of an existing quantity, and a new cell, without any
    change to its code."""
    bm = json.loads(json.dumps(BM))
    new = dict(bm["workloads"][0], name="ds67b-chat-copy")
    bm["workloads"].append(new)
    bm["per_layer"].append(dict(bm["per_layer"][0], name="idle_share.x",
                                workloads=["ds67b-chat-copy"]))
    wl = names.BENCH / "workloads" / "ds67b-chat-copy.json"
    wl.write_text((names.BENCH / "workloads" / "ds67b-chat.json")
                  .read_text())
    try:
        spec = names.cell_spec("ds67b-chat-copy", bm)
    finally:
        wl.unlink()
    assert [m["name"] for m in spec["per_layer"]] == ["idle_share.x"]
    assert names.metric_reader("idle_share.x").read


def test_bench_imports_no_other_benchmark_code():
    for path in names.BENCH.rglob("*.py"):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            mods = ([a.name for a in node.names]
                    if isinstance(node, ast.Import) else
                    [node.module or ""] if isinstance(node, ast.ImportFrom)
                    else [])
            for mod in mods:
                assert not mod.startswith(("benchmarks", "chip_smoke")), \
                    (path, mod)
                if path.parent.name in ("reference", "ops", "metrics",
                                        "lib"):
                    assert not mod.startswith("repro"), (path, mod)


def test_run_refuses_the_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(names.BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=names.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("mix", ["chat", "long_decode"])
def test_request_stream_is_a_pure_function_of_the_seed(mix):
    m = json.loads((names.BENCH / "traffic" / f"{mix}.json").read_text())
    big = 2 ** 31 + 12345
    a = traffic.lm_requests(m, big, 64, 4096)
    assert a == traffic.lm_requests(m, big, 64, 4096)
    b = traffic.lm_requests(m, 7, 64, 4096)
    assert a != b
    # every seed gets the same sizes and gaps, in its own order
    for key in ("prompt_len", "max_new"):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
    if m.get("stagger"):
        # the clients' first requests start at phases spread evenly over
        # their answers; the rest start from their prompts
        k = m["clients"]
        for s in (a, b):
            assert sorted(int(r["answered"] * k / r["max_new"])
                          for r in s[:k]) == list(range(k))
            assert all(r["answered"] == 0 for r in s[k:])
    else:
        assert all(r["answered"] == 0 for r in a)
    if m["loop"] == "open":
        gaps = lambda s: sorted(round(x, 9) for x in __import__("numpy").diff(
            [0.0] + [r["due_s"] for r in s]))
        assert gaps(a) == gaps(b)
    assert all(r["prompt_len"] + r["max_new"] <= 4096 for r in a)
    assert (traffic.prompt_tokens(big, 5, 100, 102400)
            == traffic.prompt_tokens(big, 5, 100, 102400)).all()
    assert 0 <= traffic.key_seed(big) < 2 ** 31


def test_annotate_refuses_an_engine_without_its_callables():
    from bench.drivers import paged_lm

    class Engine:
        def _admit(self):
            pass

    with pytest.raises(AttributeError):
        paged_lm._Annotate(Engine())
