"""The CIM execution contract, stated declaratively.

This module is the ONE place where "a full-plan dense decode block is 6
fused Pallas dispatches" lives.  Every structural test and the
``make audit`` registry sweep derive their expected numbers from here,
so a PR that legitimately changes a dispatch count is a one-line,
reviewed edit to this file instead of a hunt through test modules.

The contract is stated per *logical site class*, not per kernel
function:

=============  =====================================================
site class     kernel functions
=============  =====================================================
quantize       ``_rowquant_kernel`` (standalone row-absmax int8)
fused_gemm     ``_cim_gemm_fused_qin_kernel`` / ``_cim_gemm_fused_kernel``
               / ``_cim_gated_kernel`` (full dequant/bias/act/residual
               epilogue in-kernel)
acc_gemm       ``_cim_gemm_kernel`` — int32-accumulator partial GEMM;
               only legal under TP row-parallel, feeding the exact
               cross-shard ``psum``
grouped_moe    ``_cim_grouped_gemm_kernel`` / ``_cim_grouped_gated_kernel``
decode_attn    ``_decode_kernel`` / ``_decode_paged_kernel`` /
               ``_decode_splitkv_kernel`` / ``_mla_decode_kernel``
attn_combine   ``_combine_kernel`` (split-KV log-sum-exp merge)
=============  =====================================================

Expected counts are *derived from the config dims* using the same
thresholds the kernel wrappers branch on (``MAX_FUSED_QUANT_K/N``): at
reduced test dims a dense decode block is 6 dispatches, while e.g.
full-size gemma-2b (d_ff 16384 > MAX_FUSED_QUANT_N) legitimately takes
a 7th — a standalone hidden requant the fused epilogue cannot hold in
VMEM.  Encoding the rule rather than per-arch numbers keeps one
manifest honest at every scale.
"""
from __future__ import annotations

from collections import Counter

from repro.kernels.cim_gemm import (CORE_K, CORE_N, MAX_FUSED_QUANT_K,
                                    MAX_FUSED_QUANT_N)

# decode_attention auto-splits the KV range above this many cache slots
# (kernels/ops.py): the combine kernel then joins the partial softmaxes.
SPLITKV_THRESHOLD = 2048

SITE_CLASSES = ("quantize", "fused_gemm", "acc_gemm", "grouped_moe",
                "decode_attn", "attn_combine")

KERNEL_SITES = {
    "_rowquant_kernel": "quantize",
    "_cim_gemm_fused_qin_kernel": "fused_gemm",
    "_cim_gemm_fused_kernel": "fused_gemm",
    "_cim_gated_kernel": "fused_gemm",
    "_cim_gemm_kernel": "acc_gemm",
    "_cim_grouped_gemm_kernel": "grouped_moe",
    "_cim_grouped_gated_kernel": "grouped_moe",
    "_decode_kernel": "decode_attn",
    "_decode_paged_kernel": "decode_attn",
    "_decode_splitkv_kernel": "decode_attn",
    "_mla_decode_kernel": "decode_attn",
    "_combine_kernel": "attn_combine",
}

# GEMM-family kernels: which BlockSpec-mapped operands are the int8
# weight stacks whose block shapes must respect the CIM core geometry
# (indices into grid_mapping.block_mappings, scalar-prefetch excluded).
WEIGHT_BLOCK_OPERANDS = {
    "_cim_gemm_kernel": (1,),
    "_cim_gemm_fused_kernel": (1,),
    "_cim_gemm_fused_qin_kernel": (1,),
    "_cim_gated_kernel": (1, 2),
    "_cim_grouped_gemm_kernel": (1,),
    "_cim_grouped_gated_kernel": (1, 2),
}

# Site classes that must carry a scalar-prefetch operand in a traced
# step: the grouped MoE kernels read the expert skip list
# (``expert_counts``) and the paged/ring decode kernels read positions /
# block tables ahead of the grid.  Dropping the prefetch silently turns
# the zero-capacity skip into dead MXU work, so the dispatch audit pins
# it here.
PREFETCH_REQUIRED = {"grouped_moe", "decode_attn"}

# ---------------------------------------------------------------------------
# VMEM / geometry budget
# ---------------------------------------------------------------------------
# Static per-dispatch VMEM ceiling: every mapped block + scratch must
# fit the TPUConfig VMEM size.  This is the single-buffered footprint —
# the compiler needs slack to double-buffer, so WARN_FRACTION marks the
# "you are relying on the scheduler's mercy" zone; the audit only FAILS
# above the hard budget.  Interpret-mode block guesses (ROADMAP item 5)
# get their hard ceiling here until the autotuner lands.


def vmem_budget_bytes() -> int:
    from repro.core.hardware import TPUConfig
    return TPUConfig().vmem_bytes


VMEM_WARN_FRACTION = 0.5


# ---------------------------------------------------------------------------
# Expected collectives under a model-axis mesh
# ---------------------------------------------------------------------------
# Per sharded transformer block (dense and MoE alike): the two
# row-parallel GEMMs (attn out-proj, MLP down) each stage one f32
# ``pmax`` (global row-absmax so every shard quantizes against the same
# scale) and one int32 ``psum`` (exact partial-accumulator sum before
# the single epilogue).  Anything else on the model axis — above all an
# all-gather of weights or activations — breaks the TP contract.
TP_AXIS = "model"
BLOCK_TP_COLLECTIVES = {("pmax", (TP_AXIS,)): 2, ("psum", (TP_AXIS,)): 2}
ALLOWED_COLLECTIVE_OPS = frozenset({"pmax", "psum"})
# The exactness contract: cross-shard accumulator sums must be integer.
PSUM_DTYPE = "int32"


def _pad(dim: int, mult: int) -> int:
    return -(-dim // mult) * mult


def gemm_in_sites(k_dim: int) -> Counter:
    """Dispatches for one fused GEMM taking a float activation of inner
    dim ``k_dim`` (kernels/ops.py `cim_quantized_matmul_fused`): the
    activation quantize rides in-kernel until the f32 row block would
    blow the VMEM budget, then becomes a standalone quantize."""
    if _pad(k_dim, CORE_K) <= MAX_FUSED_QUANT_K:
        return Counter({"fused_gemm": 1})
    return Counter({"fused_gemm": 1, "quantize": 1})


def mlp_sites(d_ff: int, grouped: bool = False) -> Counter:
    """Dispatches for one fused MLP pipeline (gated or not — both are
    quantize + front GEMM + down GEMM): the mid-pipeline requant rides
    the front GEMM's epilogue until the full hidden row exceeds
    ``MAX_FUSED_QUANT_N``, then becomes a standalone quantize."""
    gemm = "grouped_moe" if grouped else "fused_gemm"
    n_q = 1 if _pad(d_ff, CORE_N) <= MAX_FUSED_QUANT_N else 2
    return Counter({"quantize": n_q, gemm: 2})


def _moe_dims(cfg):
    mo = cfg.moe
    shared_ff = None
    if mo.n_shared_experts:
        shared_ff = mo.shared_d_ff or mo.d_expert * mo.n_shared_experts
    return mo.d_expert, shared_ff


def block_sites(cfg, spec, phase: str, sharded: bool = False,
                kv_len: int = 0, paged: bool = False) -> Counter:
    """Expected site-class dispatch counts for ONE transformer block.

    ``spec`` is the ``(mixer, ffn)`` pair from ``Model.groups``;
    ``phase`` is ``"prefill"`` / ``"decode"`` / ``"step"`` (DiT).
    ``sharded`` states the step is traced under a model-axis mesh
    (per-shard counts); ``kv_len`` is the attended cache length (decides
    split-KV); ``paged`` that decode runs over the paged cache (MLA's
    latent kernel runs there only; its ring decode is jnp).
    """
    mixer, ffn = spec
    if mixer not in ("attn", "attn_local", "mla"):
        raise ValueError(f"no full-plan contract for mixer {mixer!r}")
    sites: Counter = Counter()
    if mixer == "mla":
        if sharded:
            raise ValueError("MLA is data-parallel: no TP contract")
        m = cfg.mla
        # q_a | kv_a as one wide GEMM, q_b, out-projection
        sites += gemm_in_sites(cfg.d_model)
        sites += gemm_in_sites(m.q_lora_rank)
        sites += gemm_in_sites(cfg.n_heads * m.v_head_dim)
        if phase == "decode" and paged:
            sites["decode_attn"] += 1
    else:
        # attention: QKV projection + decode kernel + out projection
        if sharded:
            sites += gemm_in_sites(cfg.d_model)      # column-parallel QKV
            sites["acc_gemm"] += 1                   # row-parallel out
        else:
            sites += gemm_in_sites(cfg.d_model)
            sites += gemm_in_sites(cfg.n_heads * cfg.head_dim)
        if phase == "decode":
            sites["decode_attn"] += 1
            if kv_len > SPLITKV_THRESHOLD:
                sites["attn_combine"] += 1
    # feed-forward
    if ffn == "dense":
        if sharded:
            # column front (quantize + gated/fused GEMM) + row down
            # (XLA global row-quant, int32 acc kernel)
            sites["quantize"] += 1
            sites["fused_gemm"] += 1
            sites["acc_gemm"] += 1
        else:
            sites += mlp_sites(cfg.d_ff)
    elif ffn == "moe":
        d_expert, shared_ff = _moe_dims(cfg)
        # expert-parallel sharding keeps each expert's dims intact, so
        # the routed pipeline is the unsharded grouped profile either way
        sites += mlp_sites(d_expert, grouped=True)
        if shared_ff is not None:
            if sharded:
                sites["quantize"] += 1
                sites["fused_gemm"] += 1
                sites["acc_gemm"] += 1
            else:
                sites += mlp_sites(shared_ff)
    elif ffn != "none":
        raise ValueError(f"no full-plan contract for ffn {ffn!r}")
    return sites


def model_sites(model, phase: str, sharded: bool = False,
                kv_len: int = 0, paged: bool = False) -> Counter:
    """Expected dispatch counts for one whole-model step.  Stacked layer
    groups scan over a single traced block body, so each group
    contributes its per-block profile exactly once regardless of
    depth — depth-free dispatch counts are themselves part of the
    contract (checked by tracing, not assumed)."""
    total: Counter = Counter()
    for spec, _count in model.groups:
        total += block_sites(model.cfg, spec, phase, sharded=sharded,
                             kv_len=kv_len, paged=paged)
    return total


def dit_sites(cfg, sharded: bool = False) -> Counter:
    """Expected per-step counts for a DiT block: adaLN modulation GEMM
    (bias in epilogue) + QKV + out-projection + MLP pipeline.  Like the
    LM groups, the N blocks scan over stacked params, so the whole
    forward traces one block body."""
    if sharded:
        raise ValueError("DiT TP audit not in the contract matrix yet")
    q_dim = cfg.n_heads * cfg.head_dim
    sites = gemm_in_sites(cfg.d_model)               # adaLN (cond vector)
    sites += gemm_in_sites(cfg.d_model)              # QKV
    sites += gemm_in_sites(q_dim)                    # out-proj
    sites += mlp_sites(cfg.d_ff)
    return sites


def supports_full_plan(model) -> bool:
    """True when every layer group of the model has a contract entry
    (attention or MLA mixer + dense/moe/none ffn) — the archs `make
    audit` must cover.  SSM / xLSTM mixers are ROADMAP item R2."""
    for spec, _count in model.groups:
        mixer, ffn = spec
        if mixer not in ("attn", "attn_local", "mla"):
            return False
        if ffn not in ("dense", "moe", "none"):
            return False
    return True


def mlp_pipeline_dispatches(d_ff: int, grouped: bool = False) -> int:
    """Total dispatches of one standalone fused MLP pipeline — what the
    kernel-level structural tests pin."""
    return sum(mlp_sites(d_ff, grouped=grouped).values())
