"""The ``dsv3-long-decode`` cell cut to a size the CPU runs: its driver,
reference and check end to end, the int4 control, the configuration the
program builds against the file's published keys, and the operation
counts of ``bench/ops/deepseek_v3.py``.

The cut keeps every MLA, router and expert width (the program takes
them from its registry, as on the chip) and shrinks the hidden size,
heads, dense FFN, vocabulary, engine and traffic.
"""
import pytest

from bench import run as bench_run
from bench.lib import names
from bench.lib.record import Run
from bench.lib.trace import Trace
from bench.metrics import mla_decode_roofline, moe_gemm_roofline
from bench.ops import deepseek_v3 as ops
from bench.tests import tiny

CELL = "dsv3-long-decode"
SEED = 2 ** 31 + 77
PEAK = {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9}


def _spec(hidden=64, vocab=256):
    s = names.cell_spec(CELL)
    cfg, mix, eng = s["config"], s["traffic"], s["workload"]["engine"]
    cfg.update(hidden_size=hidden, num_attention_heads=4,
               num_key_value_heads=4, intermediate_size=128,
               vocab_size=vocab)
    eng.update(slots=4, max_len=256, block_size=16, prefill_chunk=32)
    mix.update(clients=4)
    mix["prompt_tokens"].update(min=16, max=64)
    return s


def test_cpu_cut_runs_and_is_correct():
    res = bench_run.run_cell(_spec(), SEED, 1.0, False, 0.0, tiny.DEVICE)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}


def test_lower_precision_control_is_not_correct():
    """The reference in int4 where the program is int8 reads above the
    cell's limit (at a width where int4 error is as large, relative to
    the logits, as at the cell's size)."""
    spec = _spec(hidden=256, vocab=2048)
    out = names.driver(spec["config"]["engine"]).run(
        spec, SEED, 1.0, None, 0.0, tiny.DEVICE, bench_run.log,
        control_bits=4)
    limit = spec["workload"]["check"]["limit"]
    assert out["checks"]["logit_gap"]["value"] <= limit
    assert out["checks"]["control"]["value"] > limit


def test_program_builds_the_configuration():
    """The driver builds the model from ``program_arch``; its MLA, YaRN,
    router and expert share are the file's published keys."""
    from bench.drivers.paged_lm import program_model
    cfg = names.cell_spec(CELL)["config"]
    m = program_model(cfg).cfg
    rs = cfg["rope_scaling"]
    assert (m.mla.q_lora_rank, m.mla.kv_lora_rank, m.mla.qk_nope_head_dim,
            m.mla.qk_rope_head_dim, m.mla.v_head_dim) == (
        cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    assert (m.mla.rope_factor, m.mla.original_max_position, m.mla.beta_fast,
            m.mla.beta_slow, m.mla.mscale, m.mla.mscale_all_dim) == (
        rs["factor"], rs["original_max_position_embeddings"],
        rs["beta_fast"], rs["beta_slow"], rs["mscale"], rs["mscale_all_dim"])
    mo = m.moe
    assert (mo.n_routed_experts, mo.top_k, mo.d_expert, mo.n_shared_experts,
            mo.first_k_dense, mo.n_group, mo.topk_group,
            mo.routed_scaling_factor, mo.norm_topk_prob, mo.scoring) == (
        cfg["n_routed_experts"], cfg["num_experts_per_tok"],
        cfg["moe_intermediate_size"], cfg["n_shared_experts"],
        cfg["first_k_dense_replace"], cfg["n_group"], cfg["topk_group"],
        cfg["routed_scaling_factor"], cfg["norm_topk_prob"],
        "sigmoid_group")
    assert (mo.n_held, mo.expert_shard, mo.n_expert_shards) == (
        cfg["n_routed_experts_held"], cfg["expert_shard"],
        cfg["n_expert_shards"])
    assert (m.n_layers, m.d_model, m.n_heads, m.d_ff, m.vocab) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["intermediate_size"],
        cfg["vocab_size"])


def test_mla_decode_counts():
    """Absorbed latent decode, bf16 MXU operands: per attended position
    2*H*(512 + 64) score and 2*H*512 output flops, 512 + 64 int8 bytes
    and two f32 scales -- about 477 flops a byte at 128 heads."""
    cfg = names.cell_spec(CELL)["config"]
    c = ops.latent_decode(cfg, [1000, 3000])
    assert c["bf16_ops"] == 4000 * 128 * (2 * 576 + 2 * 512)
    assert c["bytes"] == 4000 * (576 + 8) + 2 * 2 * 128 * (2 * 512 + 64)
    assert c["bf16_ops"] / (4000 * 584) == pytest.approx(477, abs=1)
    # bound by compute: 0.87 GFLOP at 197 TFLOP/s
    from bench.ops import gemm
    assert gemm.least_s(c, PEAK) == pytest.approx(c["bf16_ops"] / 197e12)


def test_mla_prefill_counts():
    """A 256-token chunk at offset 1024 in the absorbed form: each query
    at position p attends p + 1 positions, 2*H*(512 + 64) + 2*H*512
    flops each; the W_UK / W_UV folds run in XLA."""
    cfg = names.cell_spec(CELL)["config"]
    step = ops.prefill_chunk(cfg, 256, 1024, last=False)
    keys = sum(1024 + i + 1 for i in range(256))
    (n, call), = step["mla_prefill"]
    assert n == 7
    assert call["bf16_ops"] == keys * 128 * (2 * 576 + 2 * 512)
    assert [c["bf16_ops"] for _, c in step["mla_xla"]] == [
        2 * 256 * 128 * 128 * 512, 2 * 256 * 128 * 512 * 128]
    assert set(step) == {"gemm", "moe", "mla_prefill", "mla_xla", "head"}


def test_expected_touched_experts():
    """Uniform routing of 32 rows, 8 of 256 experts each, 8 held: a
    held expert is untouched with probability (1 - 8/256)**32 ~ 0.36."""
    x = ops.dims(names.cell_spec(CELL)["config"])
    rows, touched = ops.expected_experts(x, 32)
    assert rows == pytest.approx(32 * 8 * 8 / 256)
    assert touched == pytest.approx(8 * (1 - (31 / 32) ** 32))
    assert 5.0 < touched < 5.2


def test_gemm_family_holds_no_expert_call():
    """``gemm`` holds the fused / gated GEMMs only (MLA projections, the
    dense FFN, the shared expert); the routed experts are ``moe``."""
    cfg = names.cell_spec(CELL)["config"]
    step = ops.decode(cfg, [2048] * 32)
    d, F = 7168, 2048
    per_tok = sum(n * c["int8_ops"] for n, c in step["gemm"]) / 32
    mla = 2 * d * 2112 + 2 * 1536 * 128 * 192 + 2 * 128 * 128 * d
    assert per_tok == 7 * mla + 3 * 3 * 2 * d * 18432 + 4 * 3 * 2 * d * F
    moe = sum(n * c["int8_ops"] for n, c in step["moe"])
    assert moe == pytest.approx(4 * 3 * 2 * d * F * 32 * 8 * 8 / 256)
    assert set(step) == {"gemm", "moe", "mla", "mla_xla", "head"}


def _traced_run(family_calls, op_name, dur_ns):
    run = Run(kind="lm", config={}, peaks=PEAK, ops=ops)
    run.steps = [{"t0": 0.0, "t1": 1.0, "work": [family_calls]}]
    run.traced = (0.0, 1.0)
    run.traced_ns = (0.0, 1e9)
    run.trace = Trace(ops=[(1e6, 1e6 + dur_ns, f"{op_name}.3")])
    return run


@pytest.mark.parametrize("reader,family,op", [
    (mla_decode_roofline, "mla", "mla_decode_paged"),
    (moe_gemm_roofline, "moe", "cim_grouped_gated_gemm_int8")])
def test_new_readers(reader, family, op):
    """Least time over the named kernels' device time; nothing without
    a trace or without the kernel."""
    call = {"int8_ops": 0.0, "bf16_ops": 197e12 * 1e-3, "bytes": 0.0}
    run = _traced_run({family: [(1, call)]}, op, 4e6)
    assert reader.read(run) == pytest.approx(25.0)
    assert reader.read(_traced_run({family: [(1, call)]}, "fusion",
                                   4e6)) is None
    run.trace = None
    assert reader.read(run) is None
