"""Tests for the configs -> simulator bridge and the mapping engine."""
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ARCH_IDS, get_config
from repro.core import (MatMulOp, OpKind, get_hardware, map_matmul,
                        simulate_graph, tpuv4i_baseline)
from repro.core.bridge import graph_from_config

BASE = tpuv4i_baseline()
CIM = get_hardware("cim-16x8")


class TestBridge:
    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_graphs_build_and_cost(self, arch):
        cfg = get_config(arch)
        dec = graph_from_config(cfg, batch=4, q_len=1, kv_len=512)
        pre = graph_from_config(cfg, batch=4, q_len=512, kv_len=512)
        assert len(dec.ops) > cfg.n_layers          # >1 op per layer
        assert pre.total_macs > dec.total_macs      # prefill >> decode
        c_dec = simulate_graph(BASE, dec)
        c_pre = simulate_graph(BASE, pre)
        assert 0 < c_dec.latency_s < c_pre.latency_s
        assert c_dec.mxu_energy_j > 0

    def test_quant_plan_bits_mirror_execution(self):
        """graph_from_config(quant_plan=...) must cost exactly what
        apply_plan quantizes: attn/attn_local projections INT8, the
        KV-cache GEMVs INT8 when ``attn_kv`` covers them (int8 KV
        streamed through the flash-decode kernel), MLA projections INT8
        under ``mla_proj``/``mla_out`` with W_UK/W_UV and the latent
        products bf16, MoE shared experts follow
        ``moe_experts``, router/head bf16."""
        from repro.quant import QuantPlan
        full = QuantPlan.full()

        g = graph_from_config(get_config("gemma-2b"), 4, 1, 512,
                              quant_plan=full)
        by_kind = {}
        for op in g.matmuls:
            by_kind.setdefault(op.kind, set()).add(op.act_bits)
        assert by_kind[OpKind.QKV] == {8}
        assert by_kind[OpKind.PROJ] == {8}
        assert by_kind[OpKind.FFN] == {8}
        assert by_kind[OpKind.ATTN_QK] == {8}        # int8 KV-cache GEMVs
        assert by_kind[OpKind.ATTN_SV] == {8}
        assert by_kind[OpKind.LM_HEAD] == {16}

        # attn_kv off: the KV GEMVs fall back to bf16 while the
        # projections stay covered
        import dataclasses
        no_kv = dataclasses.replace(full, attn_kv=False)
        g = graph_from_config(get_config("gemma-2b"), 4, 1, 512,
                              quant_plan=no_kv)
        by_kind = {}
        for op in g.matmuls:
            by_kind.setdefault(op.kind, set()).add(op.act_bits)
        assert by_kind[OpKind.ATTN_QK] == {16}
        assert by_kind[OpKind.QKV] == {8}

        # MLA (deepseek): mla_proj / mla_out put q_down, q_up, kv_down
        # and o on the int8 pipeline; W_UK/W_UV (q_absorb, v_up) and the
        # latent score/value products stay bf16 — the simulator agrees.
        g = graph_from_config(get_config("deepseek-v3-671b"), 4, 1, 512,
                              quant_plan=full)
        mla_bits = {o.name.rsplit(".", 1)[-1]: o.act_bits
                    for o in g.matmuls if ".mla." in o.name}
        assert {k: mla_bits[k] for k in ("q_down", "q_up", "kv_down",
                                         "o")} == dict.fromkeys(
            ("q_down", "q_up", "kv_down", "o"), 8)
        assert {mla_bits[k] for k in ("q_absorb", "v_up", "qk",
                                      "sv")} == {16}
        assert {o.act_bits for o in g.matmuls
                if o.kind == OpKind.MOE_FFN} == {8}
        assert {o.act_bits for o in g.matmuls if o.kind == OpKind.FFN
                and "shared" in o.name} == {8}

        # mlp_only leaves the MoE shared expert (moe_experts-covered,
        # not mlp-covered) at bf16
        g = graph_from_config(get_config("qwen2-moe-a2.7b"), 4, 1, 512,
                              quant_plan=QuantPlan.mlp_only())
        assert {o.act_bits for o in g.matmuls if o.kind == OpKind.FFN
                and "shared" in o.name} == {16}

        # no plan: the bits argument applies unchanged (default 8)
        g = graph_from_config(get_config("gemma-2b"), 4, 1, 512)
        assert {o.act_bits for o in g.matmuls} == {8}

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_cim_never_catastrophically_worse(self, arch):
        """CIM decode should be within 2x of baseline for every family
        (the paper's technique applies everywhere; xLSTM is the worst)."""
        cfg = get_config(arch)
        g = graph_from_config(cfg, batch=8, q_len=1, kv_len=1280)
        b = simulate_graph(BASE, g)
        c = simulate_graph(CIM, g)
        assert c.latency_s < 2.0 * b.latency_s
        # energy always improves by a lot
        assert b.mxu_energy_j / c.mxu_energy_j > 4.0

    def test_decode_flops_scale_with_kv(self):
        cfg = get_config("command-r-plus-104b")
        g1 = graph_from_config(cfg, 4, 1, 1024)
        g2 = graph_from_config(cfg, 4, 1, 4096)
        attn1 = sum(o.macs for o in g1.matmuls
                    if o.kind in (OpKind.ATTN_QK, OpKind.ATTN_SV))
        attn2 = sum(o.macs for o in g2.matmuls
                    if o.kind in (OpKind.ATTN_QK, OpKind.ATTN_SV))
        assert attn2 == pytest.approx(4 * attn1, rel=0.01)

    def test_sliding_window_caps_attention(self):
        cfg = get_config("gemma3-4b")
        g = graph_from_config(cfg, 4, 1, 32768)
        for op in g.matmuls:
            if "attn_local" in op.name and op.kind == OpKind.ATTN_QK:
                assert op.N <= cfg.sliding_window

    def test_mla_decode_uses_latent_dims(self):
        cfg = get_config("deepseek-v3-671b")
        g = graph_from_config(cfg, 4, 1, 1024)
        qk = [o for o in g.matmuls if o.kind == OpKind.ATTN_QK]
        assert qk, "MLA graph must contain score GEMVs"
        r = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
        assert all(o.K == r for o in qk)  # scores against the latent


class TestMappingEngine:
    def test_traffic_at_least_compulsory(self):
        op = MatMulOp(name="g", kind=OpKind.FFN, M=4096, K=4096, N=4096)
        m = map_matmul(BASE, op, compute_s=1e-3)
        compulsory = op.input_bytes + op.weight_bytes + op.output_bytes
        assert m.hbm_bytes >= 0.99 * compulsory

    def test_residency_beats_streaming_for_big_weights(self):
        """A-resident mapping avoids re-reading activations when the
        weight matrix exceeds CMEM (the paper's Fig 5 case)."""
        op = MatMulOp(name="g", kind=OpKind.FFN, M=8192, K=7168, N=28672)
        m = map_matmul(BASE, op, compute_s=1e-3)
        compulsory = op.input_bytes + op.weight_bytes + op.output_bytes
        # within 2x of compulsory even though weights are 205MB > CMEM
        assert m.hbm_bytes < 2.0 * compulsory

    def test_tiles_fit_cmem(self):
        op = MatMulOp(name="g", kind=OpKind.FFN, M=8192, K=7168, N=28672)
        m = map_matmul(BASE, op, compute_s=1e-3)
        mt, kt, nt = m.cmem_tile
        bytes_needed = mt * kt + kt * nt + mt * nt * 4
        assert 2 * bytes_needed <= BASE.cmem_bytes

    @given(m=st.sampled_from([1, 8, 512, 8192]),
           k=st.sampled_from([512, 7168]),
           n=st.sampled_from([512, 28672]))
    @settings(max_examples=12, deadline=None)
    def test_mapping_invariants(self, m, k, n):
        op = MatMulOp(name="p", kind=OpKind.FFN, M=m, K=k, N=n)
        for hw in (BASE, CIM):
            mp = map_matmul(hw, op, compute_s=1e-4)
            assert mp.hbm_bytes >= 0
            assert mp.oci_bytes >= 0
            assert mp.startup_s >= 0
