"""deepseek-v3-671b [moe] — 61L d_model=7168 128H d_ff=2048 (per expert)
vocab=129280, MoE 256e top-8 — MLA, 1 shared + 256 routed top-8
[arXiv:2412.19437; huggingface.co/deepseek-ai/DeepSeek-V3 config.json]

As published: the first 3 layers dense with d_ff=18432; the ``noaux_tc``
router (sigmoid scores plus ``e_score_correction_bias``, 4 of 8 expert
groups, normalised top-8 gates times 2.5); YaRN rope (factor 40 over an
original 4096 positions, beta_fast 32, beta_slow 1, mscale and
mscale_all_dim 1) for the 163,840-token context.  Departure: the
multi-token-prediction (MTP) layer is not built; it serves speculative
decoding (ROADMAP R6), and the single next-token head is the model.
Optimizer moments run in bf16 for this config (see configs/shapes.py) so
the 671B training state fits the 512-chip dry-run budget.
"""
from repro.models.mla import MLAConfig
from repro.models.moe import MoEConfig

from .base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    head_dim=128,
    d_ff=18432,                 # dense layers (first 3)
    vocab=129280,
    activation="swiglu",
    norm="rmsnorm",
    rope_theta=10000.0,
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512,
                  qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128, rope_factor=40.0,
                  original_max_position=4096, beta_fast=32.0,
                  beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
    moe=MoEConfig(n_routed_experts=256, top_k=8, d_expert=2048,
                  n_shared_experts=1, shared_d_ff=2048,
                  capacity_factor=1.25, norm_topk_prob=True,
                  first_k_dense=3, scoring="sigmoid_group", n_group=8,
                  topk_group=4, routed_scaling_factor=2.5),
    family="moe",
    # MLA latent cache (576 B/token/layer) keeps 500k-context decode
    # feasible; cache seq is context-parallel over the data axis.
    long_context_capable=True,
    train_microbatches=8,
)
