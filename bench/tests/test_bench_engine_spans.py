"""The engine's profiler spans and the readers of them: a trace of a
tiny paged engine recorded here on the CPU, and hand-made intervals."""
import shutil
import tempfile
from types import SimpleNamespace

import numpy as np
import pytest

from bench.lib import engine_spans, trace
from bench.lib.record import Run
from bench.metrics import admit_wait_p50_s, decode_sample_ms, host_step_ms

PHASES = ("engine.admit", "engine.prefill.dispatch", "engine.prefill.fetch",
          "engine.prefill.sample", "engine.decode.dispatch",
          "engine.decode.fetch", "engine.decode.sample", "engine.release")


@pytest.fixture(scope="module")
def served():
    """A tiny paged engine serving four requests inside a traced window,
    recorded where ``bench/run.py`` records (a ``bench-trace-*``
    temporary directory) so the readers find it as in a run."""
    import jax

    from repro.configs import get_config, reduced_config
    from repro.models import build_model
    from repro.quant import kernel_mode
    from repro.serving import PagedServingEngine, Request

    cfg = reduced_config(get_config("gemma-2b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = PagedServingEngine(model, params, n_slots=3, max_len=64,
                             block_size=8, prefill_chunk=8)
    rng = np.random.default_rng(0)

    def reqs(base):
        return [Request(uid=base + i,
                        prompt=rng.integers(1, cfg.vocab, n).astype(np.int32),
                        max_new_tokens=4)
                for i, n in enumerate((5, 13, 20, 9))]

    d = tempfile.mkdtemp(prefix="bench-trace-")
    with kernel_mode(False):
        for r in reqs(100):                  # compile outside the window
            eng.submit(r)
        eng.run_until_done()
        stats0 = (eng.stats.decode_steps, eng.stats.prefill_chunks)
        batch = reqs(0)
        for r in batch:
            eng.submit(r)
        trace.start(d)
        n_steps = 0
        with jax.profiler.TraceAnnotation("bench.window"):
            while eng.pending():
                eng.step()
                n_steps += 1
        jax.profiler.stop_trace()
    tr = trace.load(d)
    win = [s for s in tr.spans if s[2] == "bench.window"][0]
    run = Run(kind="lm", config={}, peaks={}, ops=None, trace=tr,
              traced_ns=(win[0], win[1]))
    eng_spans = engine_spans.of_run(run)
    yield SimpleNamespace(
        run=run, spans=eng_spans, n_steps=n_steps, requests=batch,
        decode_steps=eng.stats.decode_steps - stats0[0],
        prefill_chunks=eng.stats.prefill_chunks - stats0[1])
    shutil.rmtree(d, ignore_errors=True)


def _named(spans, name):
    return [sp for sp in spans if sp[2] == name]


def test_one_step_span_per_step_and_phases_inside_it(served):
    steps = _named(served.spans, "engine.step")
    assert len(steps) == served.n_steps
    phases = [sp for sp in served.spans if sp[2] in PHASES]
    assert {sp[2] for sp in phases} == set(PHASES)
    for s, e, name in phases:
        assert any(s0 <= s and e <= e0 for s0, e0, _ in steps), name


def test_span_counts_match_engine_counters(served):
    assert len(_named(served.spans, "engine.decode.fetch")) \
        == served.decode_steps > 0
    assert len(_named(served.spans, "engine.prefill.dispatch")) \
        == served.prefill_chunks > 0
    assert len(_named(served.spans, "engine.prefill.fetch")) \
        == len(served.requests)


def test_span_readers_read_the_recorded_trace(served):
    sample = decode_sample_ms.read(served.run)
    host = host_step_ms.read(served.run)
    assert sample > 0 and host > 0
    steps = _named(served.spans, "engine.step")
    mean_step_ms = sum(e - s for s, e, _ in steps) / len(steps) / 1e6
    assert host <= mean_step_ms


def test_span_readers_return_nothing_without_engine_spans(served):
    run = Run(kind="lm", config={}, peaks={}, ops=None)
    assert decode_sample_ms.read(run) is None
    assert host_step_ms.read(run) is None
    # a trace whose window no temporary trace directory holds
    run.trace = served.run.trace
    run.traced_ns = (1.0, 2.0)
    assert decode_sample_ms.read(run) is None
    assert host_step_ms.read(run) is None


def test_idle_split_sums_to_the_idle_time(served):
    lo, hi = served.run.traced_ns
    ops = served.run.trace.ops
    idle = engine_spans.idle_by_span(ops, served.spans, lo, hi)
    busy = trace.busy_ns(ops, lo, hi)
    assert sum(idle.values()) == pytest.approx((hi - lo - busy) / 1e9,
                                               rel=1e-9)
    assert set(idle) <= set(PHASES) | {"engine.step", "engine.gc",
                                       "outside"}


def _req(submitted_at, admitted_at):
    return SimpleNamespace(submitted_at=submitted_at,
                           admitted_at=admitted_at)


def test_admit_wait_median_on_hand_made_run():
    run = Run(kind="lm", config={}, peaks={}, ops=None,
              t_open=100.0, t_close=160.0)
    # (submit - due) on the harness's clock plus (admitted - submitted)
    # on the engine's: 0.1 + 0.2, 0.0 + 1.0, 0.05 + 0.45; a request
    # still queued counts to the close, 160 - 150 = 10; and one admitted
    # after the close (not seen in a run) is capped there, 160 - 159.5
    run.requests = [
        {"due": 110.0, "submit": 110.1, "req": _req(7.0, 7.2)},
        {"due": 120.0, "submit": 120.0, "req": _req(3.0, 4.0)},
        {"due": 130.0, "submit": 130.05, "req": _req(9.0, 9.45)},
        {"due": 150.0, "submit": 150.0, "req": _req(2.0, None)},
        {"due": 159.5, "submit": 159.5, "req": _req(1.0, 5.0)},
    ]
    assert admit_wait_p50_s.read(run) == pytest.approx(0.5)
    run.requests.pop()
    assert admit_wait_p50_s.read(run) == pytest.approx(0.75)


def test_admit_wait_reads_nothing_from_requests_without_admission():
    run = Run(kind="lm", config={}, peaks={}, ops=None, t_close=1.0)
    run.requests = [{"due": 0.0, "submit": 0.0,
                     "req": SimpleNamespace(submitted_at=0.0)}]
    assert admit_wait_p50_s.read(run) is None
    run.requests = []
    assert admit_wait_p50_s.read(run) is None


def test_innermost_pieces_follow_nesting():
    spans = [(0, 100, "engine.step"), (10, 40, "engine.decode.sample"),
             (20, 30, "engine.gc"), (60, 70, "engine.admit")]
    pieces = engine_spans.innermost(spans, -10, 110)
    assert pieces == [(-10, 0, "outside"), (0, 10, "engine.step"),
                      (10, 20, "engine.decode.sample"),
                      (20, 30, "engine.gc"),
                      (30, 40, "engine.decode.sample"),
                      (40, 60, "engine.step"), (60, 70, "engine.admit"),
                      (70, 100, "engine.step"), (100, 110, "outside")]
    ops = [(0, 15, "a"), (35, 65, "b")]
    idle = engine_spans.idle_by_span(ops, spans, -10, 110)
    assert idle == pytest.approx({
        "outside": 20e-9, "engine.decode.sample": 10e-9,
        "engine.gc": 10e-9, "engine.admit": 5e-9, "engine.step": 30e-9})
    # the part of the sample span that the gc span nested in it covers
    assert engine_spans.covered_ns((10, 40), [(20, 30, "engine.gc")]) == 10
    assert engine_spans.covered_ns((10, 40), [(60, 70, "engine.admit")]) == 0
