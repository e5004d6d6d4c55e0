"""The trace reduction, on hand-made intervals and on a small trace
recorded here on the CPU."""
import pytest

from bench.lib import trace
from bench.lib.record import Run
from bench.metrics import gemm_roofline, idle_share, mfu
from bench.ops import gemm

PEAK = {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9}


def test_union_merges_overlaps_and_keeps_gaps():
    ops = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (30, 32, "d")]
    assert trace.union(ops) == [(0, 15), (20, 32)]
    assert trace.busy_ns(ops, 0, 40) == 27
    # clipping to the window counts only what falls inside it
    assert trace.busy_ns(ops, 8, 25) == 12


def test_kernel_sums_match_patterns_in_names_and_stats():
    ops = [(0, 10, "cim_gemm_int8_fused.1"),
           (10, 14, "quantize_rows_int8.2"),
           (14, 20, "fusion.3"),
           (20, 29, "decode_attention_paged.4")]
    ns, n = trace.kernel_ns(ops, gemm_roofline.PATTERNS, 0, 100)
    assert (ns, n) == (14, 2)
    assert trace.kernel_ns(ops, ("decode_attention_paged",), 0, 100) == (9, 1)


def test_idle_gaps_named_by_innermost_span():
    ops = [(0, 10, "a"), (40, 50, "b"), (55, 60, "c")]
    spans = [(0, 100, "bench.window"), (10, 45, "bench.step"),
             (15, 30, "bench.sample")]
    gaps = trace.idle_gaps(ops, spans, 0, 100)
    assert gaps[0] == ["bench.window", 40e-9]       # 60..100
    assert gaps[1] == ["bench.sample", 30e-9]       # 10..40, middle 25
    assert ["bench.window", 5e-9] in gaps           # 50..55


def _synthetic_run(ops, work, lo=0.0, hi=1e9):
    run = Run(kind="lm", config={}, peaks=PEAK, ops=None)
    run.steps = [{"t0": 0.0, "t1": 1.0, "work": work, "n_chunks": 0,
                  "ctx": []}]
    run.traced = (0.0, 1.0)
    run.traced_ns = (lo, hi)
    run.trace = trace.Trace(ops=ops)
    return run


def test_roofline_mfu_and_idle_from_synthetic_trace():
    call = gemm.int8_linear(256, 8192, 8192)          # 34.4 GOP, 67 MB
    least = gemm.least_s(call, PEAK)
    # one GEMM kernel that took exactly twice its least time
    ops = [(0.0, 2 * least * 1e9, "cim_gemm_int8_fused.1")]
    run = _synthetic_run(ops, [{"gemm": [(1, call)]}],
                         hi=4 * least * 1e9)
    assert gemm_roofline.read(run) == pytest.approx(50.0)
    assert idle_share.read(run) == pytest.approx(50.0)
    assert mfu.read(run) == pytest.approx(
        100 * gemm.compute_s(call, PEAK) / (4 * least))


def test_readers_return_nothing_without_a_trace_or_kernels():
    call = gemm.int8_linear(8, 64, 64)
    run = _synthetic_run([(0.0, 5.0, "fusion.1")], [{"gemm": [(1, call)]}])
    assert gemm_roofline.read(run) is None      # no GEMM kernel in trace
    run.trace = None
    assert gemm_roofline.read(run) is None
    assert mfu.read(run) is None and idle_share.read(run) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace of three matmuls inside harness spans, on the CPU."""
    import jax
    import jax.numpy as jnp
    d = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    return trace.load(str(d))


def test_recorded_cpu_trace_reduces(recorded):
    win = [s for s in recorded.spans if s[2] == "bench.window"]
    steps = [s for s in recorded.spans if s[2] == "bench.step"]
    assert len(win) == 1 and len(steps) == 3
    lo, hi = win[0][0], win[0][1]
    busy = trace.busy_ns(recorded.ops, lo, hi)
    assert 0 < busy <= hi - lo
    ns, n = trace.kernel_ns(recorded.ops, ("dot",), lo, hi)
    assert n >= 3 and 0 < ns <= busy + 1
    top = trace.top_ops(recorded.ops, lo, hi)
    assert top and top[0][1] > 0
    gaps = trace.idle_gaps(recorded.ops, recorded.spans, lo, hi)
    assert all(name.startswith("bench.") for name, _ in gaps)
    assert sum(s for _, s in gaps) == pytest.approx((hi - lo - busy) / 1e9,
                                                    rel=1e-6, abs=1e-9) \
        or len(gaps) == 10


def test_op_names_and_families_from_hlo_event_text():
    text = ("%cim_gated_gemm_int8.6 = f32[256,22016]{1,0} custom-call("
            "s8[256,8192]{1,0} %jit_quantize_rows_int8_.64)")
    assert trace.op_name(text) == "cim_gated_gemm_int8.6"
    assert trace.family("cim_gated_gemm_int8.6") == "cim_gated_gemm_int8"
    ops = [(0, 100, "while.2"), (0, 30, "cim_gemm_int8_fused.1"),
           (40, 60, "cim_gemm_int8_fused.7"), (70, 75, "fusion.3")]
    assert trace.top_ops(ops, 0, 100) == [["cim_gemm_int8_fused", 50e-9],
                                          ["fusion", 5e-9]]
