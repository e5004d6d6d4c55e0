"""FLOP and byte counts of ``bench/ops`` against hand counts."""
import pytest

from bench.lib import names
from bench.ops import attention, dit, gemm, llama

PEAK = {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9}


def _cfg(name):
    return names.cell_spec(name)["config"]


def test_deepseek_decode_qkv_gemm_hand_count():
    # 32 decode rows through the fused QKV projection of deepseek-67b:
    # K = 8192, N = (64 + 2 * 8) heads * 128 = 10240
    c = gemm.int8_linear(32, 8192, 10240)
    assert c["int8_ops"] == 2 * 32 * 8192 * 10240 == 5_368_709_120
    assert c["bytes"] == 8192 * 10240 + 4 * 10240 + 32 * 8192 + 32 * 10240
    # bandwidth-bound: 84 MB of int8 weight at 819 GB/s
    assert gemm.least_s(c, PEAK) == pytest.approx(c["bytes"] / 819e9)
    calls = llama.decode(_cfg("ds67b-decode"), [1000] * 32)["gemm"]
    assert calls[0] == (4, c)


def test_deepseek_gated_and_down_gemms():
    cfg = _cfg("ds67b-decode")
    calls = llama.layer_gemms(cfg, 256)
    gate_up = calls[2][1]
    assert gate_up["int8_ops"] == 2 * 2 * 256 * 8192 * 22016
    assert gate_up["bytes"] == (2 * 8192 * 22016 + 8 * 22016 + 256 * 8192
                                + 256 * 22016)
    down = calls[3][1]
    assert down["int8_ops"] == 2 * 256 * 22016 * 8192
    # per layer and token: 2 * (10240 + 8192 + 2 * 22016 + 22016) * 8192
    per_tok = sum(n * c["int8_ops"] for n, c in calls) / 256 / 4
    assert per_tok == 2 * 8192 * (10240 + 8192 + 3 * 22016)


def test_dit_xl2_mlp_gemm_hand_count():
    # DiT-XL/2 MLP up-projection for 16 CFG rows x 1024 tokens
    cfg = _cfg("dit-xl2-batch8")
    c = gemm.int8_linear(16 * 1024, 1152, 4608)
    assert c["int8_ops"] == 2 * 16384 * 1152 * 4608 == 173_946_175_488
    assert c["bytes"] == 1152 * 4608 + 4 * 4608 + 16384 * 1152 + 16384 * 4608
    # compute-bound: 174 GOP at 393 TOP/s
    assert gemm.least_s(c, PEAK) == pytest.approx(c["int8_ops"] / 393e12)
    ev = dit.evaluation(cfg, 16)
    assert ev["gemm"][3] == (28, c)
    # the whole evaluation: ~1.0 TOP of int8 work per row
    int8 = sum(n * x["int8_ops"] for n, x in ev["gemm"])
    assert int8 / 16 == pytest.approx(
        28 * (2 * 1152 * 6 * 1152 / 1024 + 2 * 1152 * 1152 * 12) * 1024)


def test_paged_decode_attention_counts():
    c = attention.paged_decode([100, 300], n_heads=64, n_kv_heads=8,
                               head_dim=128)
    assert c["bf16_ops"] == 4 * 64 * 128 * 400
    # int8 K and V, f32 scale per head for each, int32 position
    assert c["bytes"] == 400 * (8 * (2 * 128 + 8) + 4) + 2 * 2 * 2 * 64 * 128


def test_causal_prefill_counts_keys_attended():
    c = attention.causal_prefill(n_q=4, offset=10, n_heads=2, head_dim=8)
    # query i at position 10 + i sees 11 + i keys: 11 + 12 + 13 + 14
    assert c["bf16_ops"] == 4 * 2 * 8 * 50


def test_prefill_head_counts_only_last_chunk():
    cfg = _cfg("ds67b-chat")
    mid = llama.prefill_chunk(cfg, 256, 0, last=False)
    last = llama.prefill_chunk(cfg, 100, 256, last=True)
    assert mid["head"][0][0] == 0 and last["head"][0][0] == 1
    assert last["head"][0][1]["bf16_ops"] == 2 * 8192 * 102400
