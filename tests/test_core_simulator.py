"""Tests for the CIM-TPU architecture simulator (repro.core).

Validates the paper's headline claims (Table II, Fig 6, Fig 7, Fig 8)
against the simulator, pins its other analytic figures (Fig 2(d), the
QuantPlan's MXU energy at the 27.3x point, ECC, INT4), plus structural
invariants of the timing/energy models and the mapping engine.
"""
import dataclasses
from functools import partial

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    DEFAULT_ENERGY_MODEL as EM,
    MatMulOp, OpKind, VectorOp,
    cim_tpu, design_a, design_b, get_hardware,
    tpuv4i_baseline,
    matmul_cost, simulate_graph, simulate_op,
    llm_prefill_cost, llm_decode_cost, dit_inference_cost,
    run_exploration, pick_designs,
    pipeline_parallel_llm_cost, tensor_parallel_llm_cost,
    mxu_area_mm2,
)
from repro.configs import get_config, get_dit_config
from repro.core.bridge import dit_graph_from_config, graph_from_config
from repro.core.workloads import (ModelSpec, TransformerLayerSpec, dit_graph,
                                  dit_xl2, embed_head_graph, gpt3_30b,
                                  llm_decode_graph)
from repro.quant import QuantPlan


BASE = tpuv4i_baseline()
CIM = get_hardware("cim-16x8")


# ---------------------------------------------------------------------------
# Table II — MXU micro-comparison
# ---------------------------------------------------------------------------
class TestTableII:
    def test_peak_macs_parity(self):
        # 16384 MACs/cycle for both the 128x128 digital MXU and 16x8 CIM-MXU
        assert BASE.mxu.macs_per_cycle == 16384
        assert CIM.mxu.macs_per_cycle == 16384

    def test_energy_efficiency_ratio(self):
        dig = EM.peak_tops_per_watt(BASE)
        cim = EM.peak_tops_per_watt(CIM)
        assert dig == pytest.approx(0.77, rel=0.02)
        assert cim == pytest.approx(7.26, rel=0.02)
        assert cim / dig == pytest.approx(9.43, rel=0.02)

    def test_area_efficiency_ratio(self):
        ratio = mxu_area_mm2(BASE) / mxu_area_mm2(CIM)
        assert ratio == pytest.approx(2.02, rel=0.02)


# ---------------------------------------------------------------------------
# MXU timing model invariants
# ---------------------------------------------------------------------------
class TestMXUTiming:
    def test_systolic_large_gemm_near_peak(self):
        op = MatMulOp(name="g", kind=OpKind.FFN, M=8192, K=4096, N=4096)
        cost = matmul_cost(BASE, op)
        assert cost.util > 0.9

    def test_cim_large_gemm_near_peak(self):
        op = MatMulOp(name="g", kind=OpKind.FFN, M=8192, K=4096, N=4096)
        cost = matmul_cost(CIM, op)
        assert cost.util > 0.9

    def test_cim_and_systolic_parity_on_large_gemm(self):
        # Paper §IV-B: prefill GEMMs see no CIM latency win.
        op = MatMulOp(name="g", kind=OpKind.FFN, M=8192, K=7168, N=7168)
        dig = matmul_cost(BASE, op)
        cim = matmul_cost(CIM, op)
        assert cim.cycles == pytest.approx(dig.cycles, rel=0.15)

    def test_cim_wins_batched_gemv(self):
        # Paper §IV-B: decode attention GEMVs (unshared weights).
        op = MatMulOp(name="qk", kind=OpKind.ATTN_QK, M=1, K=128, N=1280,
                      batch=448, weights_shared=False)
        dig = matmul_cost(BASE, op)
        cim = matmul_cost(CIM, op)
        assert cim.cycles < 0.2 * dig.cycles

    def test_unshared_weights_cost_more_than_shared(self):
        shared = MatMulOp(name="s", kind=OpKind.FFN, M=64, K=1024, N=1024,
                          batch=8, weights_shared=True)
        unshared = shared.scaled(weights_shared=False)
        assert matmul_cost(BASE, unshared).cycles > matmul_cost(BASE, shared).cycles

    @given(
        m=st.integers(1, 4096), k=st.integers(1, 8192), n=st.integers(1, 8192),
        b=st.integers(1, 64), shared=st.booleans(),
    )
    @settings(max_examples=60, deadline=None)
    def test_cost_invariants(self, m, k, n, b, shared):
        op = MatMulOp(name="p", kind=OpKind.FFN, M=m, K=k, N=n, batch=b,
                      weights_shared=shared)
        for hw in (BASE, CIM):
            c = matmul_cost(hw, op)
            assert c.cycles > 0
            assert 0 <= c.util <= 1.0
            assert c.active_macs == op.macs
            # cannot beat the ensemble peak
            assert c.cycles * hw.total_mac_units >= 0.999 * op.macs

    @given(m=st.integers(1, 512), k=st.integers(64, 2048), n=st.integers(64, 2048))
    @settings(max_examples=30, deadline=None)
    def test_cim_monotone_in_cores(self, m, k, n):
        op = MatMulOp(name="p", kind=OpKind.FFN, M=m, K=k, N=n)
        small = matmul_cost(cim_tpu(8, 8, 2), op)
        large = matmul_cost(cim_tpu(16, 16, 8), op)
        # modulo the longer systolic fill of the bigger grid
        assert large.cycles <= small.cycles * 1.01 + 64


# ---------------------------------------------------------------------------
# Fig 6 — model inference evaluations (GPT-3-30B / DiT-XL/2, batch 8, INT8)
# ---------------------------------------------------------------------------
class TestFig6:
    def test_prefill_gemm_dominated(self):
        pb = llm_prefill_cost(BASE)
        frac = pb.breakdown_fractions()
        assert frac["gemm"] > 0.8  # paper: 84.9%

    def test_prefill_latency_parity_cim(self):
        pb, pc = llm_prefill_cost(BASE), llm_prefill_cost(CIM)
        assert pc.latency_s == pytest.approx(pb.latency_s, rel=0.05)

    def test_prefill_energy_reduction(self):
        pb, pc = llm_prefill_cost(BASE), llm_prefill_cost(CIM)
        ratio = pb.mxu_energy_j / pc.mxu_energy_j
        assert 8.0 < ratio < 11.0  # paper: 9.21x

    def test_decode_attention_share(self):
        db = llm_decode_cost(BASE)
        share = db.attention_latency_s() / db.latency_s
        assert 0.28 < share < 0.50  # paper: 33.7%

    def test_decode_gemv_speedup(self):
        db, dc = llm_decode_cost(BASE), llm_decode_cost(CIM)
        red = 1 - dc.attention_latency_s() / db.attention_latency_s()
        assert 0.5 < red < 0.85  # paper: 72.7%

    def test_decode_latency_reduction(self):
        db, dc = llm_decode_cost(BASE), llm_decode_cost(CIM)
        red = 1 - dc.latency_s / db.latency_s
        assert 0.2 < red < 0.45  # paper: 29.9%

    def test_decode_energy_reduction(self):
        db, dc = llm_decode_cost(BASE), llm_decode_cost(CIM)
        ratio = db.mxu_energy_j / dc.mxu_energy_j
        assert 10.0 < ratio < 18.0  # paper: 13.4x

    def test_dit_softmax_bottleneck(self):
        tb = dit_inference_cost(BASE)
        assert 0.30 < tb.breakdown["softmax"] < 0.42  # paper: 36.9%
        assert 0.30 < tb.breakdown["gemm"] < 0.45     # paper: 35.65%

    def test_dit_cim_latency_and_energy(self):
        tb, tc = dit_inference_cost(BASE), dit_inference_cost(CIM)
        red = 1 - tc.latency_s / tb.latency_s
        assert 0.0 < red < 0.15  # paper: 6.67%
        ratio = tb.mxu_energy_j / tc.mxu_energy_j
        assert 8.0 < ratio < 13.0  # paper: 10.4x


# ---------------------------------------------------------------------------
# Fig 7 — architecture exploration
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def exploration():
    return run_exploration(quadrature=2)


class TestFig7:
    def test_grid_size(self, exploration):
        assert len(exploration) == 1 + 9  # baseline + 3 dims x 3 counts

    def test_llm_diminishing_returns_16x16(self, exploration):
        rows = {r.hw.name: r for r in exploration}
        big = rows["cim-tpu-8x16x16"]
        mid = rows["cim-tpu-8x16x8"]
        gain = mid.llm.latency_s / big.llm.latency_s - 1
        assert gain < 0.10  # paper: only 2.5% improvement
        energy_up = big.llm.mxu_energy_j / mid.llm.mxu_energy_j - 1
        assert energy_up > 0.3  # paper: 95% energy increase

    def test_small_config_energy_savings(self, exploration):
        base = exploration[0]
        rows = {r.hw.name: r for r in exploration}
        small = rows["cim-tpu-2x8x8"]
        saving = base.llm.mxu_energy_j / small.llm.mxu_energy_j
        assert saving > 15.0  # paper: 27.3x

    def test_dit_scales_with_peak(self, exploration):
        rows = {r.hw.name: r for r in exploration}
        assert rows["cim-tpu-8x16x16"].dit.latency_s < \
            rows["cim-tpu-4x16x8"].dit.latency_s
        # paper: 8x(16x16) gives 33.8% reduction; ours in range
        base = exploration[0]
        red = 1 - rows["cim-tpu-8x16x16"].dit.latency_s / base.dit.latency_s
        assert 0.2 < red < 0.45

    def test_design_b_matches_paper(self, exploration):
        d = pick_designs(exploration)
        assert d["design_b"].hw.name == "cim-tpu-8x16x8"  # paper's Design B

    def test_design_a_neighborhood(self, exploration):
        # Paper picks 4x(8x8); our mapping engine finds decode more firmly
        # HBM-bound, allowing an equal-or-larger 8x8-core config.
        d = pick_designs(exploration)
        assert "8x8" in d["design_a"].hw.name


# ---------------------------------------------------------------------------
# Fig 8 — multi-device inference
# ---------------------------------------------------------------------------
class TestFig8:
    def test_pp_throughput_scales(self):
        model = gpt3_30b()
        t = [pipeline_parallel_llm_cost(BASE, model, n, quadrature=2).throughput_per_s
             for n in (1, 2, 4)]
        assert t[1] > 1.5 * t[0]
        assert t[2] > 1.5 * t[1]

    def test_design_a_beats_baseline_throughput(self):
        model = gpt3_30b()
        for n in (1, 2, 4):
            b = pipeline_parallel_llm_cost(BASE, model, n, quadrature=2)
            a = pipeline_parallel_llm_cost(design_a(), model, n, quadrature=2)
            assert a.throughput_per_s > 1.1 * b.throughput_per_s  # paper: avg 28%
            assert b.mxu_energy_j / a.mxu_energy_j > 10  # paper: 24.2x

    def test_design_b_beats_baseline_throughput(self):
        model = gpt3_30b()
        b4 = pipeline_parallel_llm_cost(BASE, model, 4, quadrature=2)
        d4 = pipeline_parallel_llm_cost(design_b(), model, 4, quadrature=2)
        assert d4.throughput_per_s > 1.2 * b4.throughput_per_s  # paper: 33%
        assert b4.mxu_energy_j / d4.mxu_energy_j > 4  # paper: 6.34x

    def test_tp_reduces_latency(self):
        model = gpt3_30b()
        t1 = tensor_parallel_llm_cost(BASE, model, 1, quadrature=2)
        t4 = tensor_parallel_llm_cost(BASE, model, 4, quadrature=2)
        assert t4.latency_s < t1.latency_s


# ---------------------------------------------------------------------------
# Paper figures the classes above leave out.  Each case holds one figure
# to the simulator's own value within 5%; the message names the paper's
# number where the paper has one.
# ---------------------------------------------------------------------------
SMALL_CIM = cim_tpu(8, 8, num_mxus=2)       # the paper's 27.3x point


def _llm_step(hw, arch, plan, kv_len=1280, em=EM):
    g = graph_from_config(get_config(arch), 8, 1, kv_len, quant_plan=plan)
    return simulate_graph(hw, g, em=em)


def _dit_step(hw, plan):
    g = dit_graph_from_config(get_dit_config("dit-xl-2"), 8, quant_plan=plan)
    return simulate_graph(hw, g)


def _llama2_13b_layer_share():
    layer = TransformerLayerSpec(d_model=5120, n_heads=40, n_kv_heads=40,
                                 head_dim=128, d_ff=13824, gated_ffn=True)
    model = ModelSpec("llama2-13b", 40, layer, vocab=32000, bits=16)
    body = simulate_graph(BASE, llm_decode_graph(model, 8, 1024)).latency_s
    edge = simulate_graph(BASE, embed_head_graph(model, 8)).latency_s
    return body / (body + edge)


def _dit_block_share():
    blocks = simulate_graph(BASE, dit_graph(dit_xl2(), 8)).latency_s
    # patchify and the final layer, costed as one embed/head graph
    aux = ModelSpec("dit-aux", 1, dit_xl2().layer, vocab=1152)
    edge = simulate_graph(BASE, embed_head_graph(aux, 8 * 1024)).latency_s
    return blocks / (blocks + edge)


def _plan_energy_gain(arch, hw, base=BASE, em=EM):
    """bf16 MXU energy of a decode step on ``base`` over the full plan's
    on ``hw``."""
    return (_llm_step(base, arch, QuantPlan.none()).mxu_energy_j
            / _llm_step(hw, arch, QuantPlan.full(), em=em).mxu_energy_j)


def _int8_kv_gain():
    full = QuantPlan.full()
    no_kv = dataclasses.replace(full, attn_kv=False)
    return (_llm_step(SMALL_CIM, "gemma-2b", no_kv, 8192).mxu_energy_j
            / _llm_step(SMALL_CIM, "gemma-2b", full, 8192).mxu_energy_j)


def _dit_plan_energy_gain(hw):
    return (_dit_step(BASE, QuantPlan.none()).mxu_energy_j
            / _dit_step(hw, QuantPlan.full()).mxu_energy_j)


def _dit_design_b_latency_reduction():
    return 1 - (_dit_step(design_b(), QuantPlan.full()).latency_s
                / _dit_step(BASE, QuantPlan.none()).latency_s)


def _int4_dit_gain(base_hw):
    """Latency cut of DiT-XL/2 at int4 on the CIM TPU against int8 on
    ``base_hw``."""
    int4 = dataclasses.replace(dit_xl2(), bits=4)
    return 1 - (simulate_graph(CIM, dit_graph(int4, 8)).latency_s
                / simulate_graph(base_hw, dit_graph(dit_xl2(), 8)).latency_s)


def _int4_llm_decode_gain():
    int4 = dataclasses.replace(gpt3_30b(), bits=4)
    return 1 - (simulate_graph(CIM, llm_decode_graph(int4, 8, 1280)).latency_s
                / simulate_graph(CIM, llm_decode_graph(gpt3_30b(), 8,
                                                       1280)).latency_s)


# name -> (computation, simulator's value, the paper's number or None)
PAPER_FIGURES = {
    "fig2d-llama2-13b-layer-share": (
        _llama2_13b_layer_share, 0.9912, "0.9835"),
    "fig2d-dit-xl2-block-share": (_dit_block_share, 0.9986, "0.9931"),
    "plan-2x8x8-vs-digital-gemma-2b": (
        partial(_plan_energy_gain, "gemma-2b", SMALL_CIM), 25.12, "27.3x"),
    "plan-2x8x8-vs-digital-qwen2-moe": (
        partial(_plan_energy_gain, "qwen2-moe-a2.7b", SMALL_CIM), 32.30,
        "27.3x"),
    "plan-16x8-vs-digital-gemma-2b": (
        partial(_plan_energy_gain, "gemma-2b", CIM), 11.88, None),
    "plan-16x8-vs-digital-qwen2-moe": (
        partial(_plan_energy_gain, "qwen2-moe-a2.7b", CIM), 15.73, None),
    "plan-vs-bf16-on-16x8-gemma-2b": (
        partial(_plan_energy_gain, "gemma-2b", CIM, CIM), 1.672, None),
    "plan-vs-bf16-on-16x8-qwen2-moe": (
        partial(_plan_energy_gain, "qwen2-moe-a2.7b", CIM, CIM), 1.734, None),
    "int8-kv-vs-bf16-kv-gemma-2b-kv8192": (_int8_kv_gain, 1.161, None),
    "dit-plan-16x8-vs-digital": (
        partial(_dit_plan_energy_gain, CIM), 15.21, None),
    "dit-plan-design-b-vs-digital": (
        partial(_dit_plan_energy_gain, design_b()), 14.68, None),
    "dit-plan-design-b-latency-reduction": (
        _dit_design_b_latency_reduction, 0.2321, "0.338 at 8x(16x16)"),
    "ecc-2x8x8-vs-digital": (
        partial(_plan_energy_gain, "gemma-2b", SMALL_CIM,
                em=EM.with_cim_ecc()),
        23.46, "27.3x without ECC"),
    "int4-dit-vs-int8-on-16x8": (partial(_int4_dit_gain, CIM), 0.2506, None),
    "int4-dit-vs-digital-int8": (partial(_int4_dit_gain, BASE), 0.2793, None),
    "int4-llm-decode-vs-int8-on-16x8": (_int4_llm_decode_gain, 0.4852, None),
}


class TestPaperFigures:
    @pytest.mark.parametrize("name", list(PAPER_FIGURES))
    def test_figure(self, name):
        compute, held, paper = PAPER_FIGURES[name]
        got = compute()
        paper_note = f"; paper {paper}" if paper else ""
        assert got == pytest.approx(held, rel=0.05), \
            f"{name} = {got:.4g}, held at {held}{paper_note}"


# ---------------------------------------------------------------------------
# Simulator structural invariants
# ---------------------------------------------------------------------------
class TestSimulatorInvariants:
    def test_latency_at_least_roofline(self):
        op = MatMulOp(name="g", kind=OpKind.FFN, M=256, K=4096, N=4096)
        c = simulate_op(BASE, op)
        hbm_floor = op.total_bytes / BASE.hbm_bandwidth
        compute_floor = op.macs / BASE.peak_macs_per_second
        assert c.latency_s >= 0.99 * max(hbm_floor * 0.5, compute_floor)

    def test_vector_op_cost(self):
        op = VectorOp(name="sm", kind=OpKind.SOFTMAX, elems=10_000_000)
        c = simulate_op(BASE, op)
        assert c.latency_s > 0
        assert c.vpu_energy_j > 0
        assert c.mxu_energy_j == 0

    def test_graph_aggregation(self):
        g = llm_decode_graph(gpt3_30b(), 8, 1280)
        cost = simulate_graph(BASE, g)
        assert cost.latency_s == pytest.approx(
            g.repeat * sum(c.latency_s for c in cost.op_costs))
        assert cost.total_energy_j > cost.mxu_energy_j

    def test_energy_positive_and_decomposed(self):
        g = llm_decode_graph(gpt3_30b(), 8, 1280)
        cost = simulate_graph(CIM, g)
        assert cost.mxu_energy_j > 0
        assert cost.memory_energy_j > 0
        assert cost.total_energy_j == pytest.approx(
            cost.mxu_energy_j + cost.vpu_energy_j + cost.memory_energy_j)

    @given(elems=st.integers(1, 10**9))
    @settings(max_examples=20, deadline=None)
    def test_vector_scaling(self, elems):
        op = VectorOp(name="v", kind=OpKind.ELEMENTWISE, elems=elems)
        c = simulate_op(BASE, op)
        assert c.latency_s >= 0
        assert c.compute_s <= c.latency_s + 1e-12
