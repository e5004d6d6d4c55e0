"""QuantPlan: a whole-model INT8 execution plan for the CIM pipeline.

The paper's CIM-MXU serves *every* matmul in the transformer block —
INT8 weights resident in the CIM macros, activations quantized by the
pre-processing unit, rescale/activation (and the residual add) in the
post-processing unit.  A :class:`QuantPlan` is the software declaration
of that architecture: it walks the model's parameter tree and states,
per logical layer kind, whether that layer executes on the fused INT8
Pallas pipeline:

    ``mlp``          dense-FFN up/gate/down     (quantize + 2 fused GEMMs)
    ``attn_qkv``     q/k/v projections          (ONE wide fused GEMM,
                                                 split after — quantize
                                                 happens in-kernel)
    ``attn_out``     attention out-projection   (one fused GEMM with the
                                                 block residual added in
                                                 its epilogue)
    ``moe_experts``  routed expert MLPs (+ the shared expert)
                                                (ONE grouped pipeline over
                                                 the stacked capacity
                                                 buffers — dispatches
                                                 constant in E)
    ``adaln``        DiT adaLN modulation GEMM  (c -> 6*d shift/scale/gate
                                                 parameters; one fused
                                                 quantize-in-kernel GEMM
                                                 with the bias in its
                                                 epilogue — diffusion
                                                 blocks only, see
                                                 models/dit.py)
    ``mla_proj``     MLA q_a/kv_a + q_b         (q_a and kv_a as ONE wide
                                                 fused GEMM on the block
                                                 input, q_b a second;
                                                 kv_b = W_UK/W_UV stays
                                                 bf16, folded around the
                                                 latent decode kernel)
    ``mla_out``      MLA out-projection         (one fused GEMM with the
                                                 block residual added in
                                                 its epilogue)
    ``attn_kv``      decode KV cache + GEMVs    (KV stored int8 at the
                                                 cache-update site, the
                                                 flash-decode kernel
                                                 dequantizes in-kernel;
                                                 no weights rewritten —
                                                 this kind covers the
                                                 cache dtype and the
                                                 QK/SV attention GEMVs'
                                                 simulator costing; on an
                                                 MLA layer, the int8
                                                 latent pools)

:func:`apply_plan` rewrites covered weights into
:class:`~repro.quant.linear.QuantizedLinear` leaves; the model layers
(``attention_apply``, ``mlp_apply``, ``moe_apply``) detect those leaves
and dispatch the fused kernels uniformly — no per-callsite flags.  With
the full plan, one decode step of a dense attention+MLP block is exactly
6 Pallas dispatches (1 QKV, 1 flash-decode attention over the int8 KV
cache, 1 out-proj w/ residual, 3 MLP); an MoE block adds a constant 3
for ALL routed experts (quantize + grouped gated GEMM + grouped down
GEMM — the expert index is a kernel grid dimension, so 60- or 256-expert
layers trace the same kernels as 4-expert ones) plus 3 for the
shared-expert MLP (9 total).  The int32 accumulators/int8 intermediates
never surface in XLA.  Both dispatch invariants are structurally pinned
in tests/test_quant.py.

Entry points: ``Model.quantize(params, plan)`` and
``ServingEngine(quant_plan=...)``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax

from .linear import (QuantizedLinear, quantize_attention, quantize_mla,
                     quantize_mlp, quantize_moe_experts)

LAYER_KINDS = ("mlp", "attn_qkv", "attn_out", "attn_kv", "moe_experts",
               "adaln", "mla_proj", "mla_out")

# The layer kinds a DiT (diffusion-transformer) block draws on: the adaLN
# modulation GEMM plus the same attention/MLP projections as a dense LLM
# block.  ``DiTModel.quantize`` and the simulator's
# ``dit_graph_from_config`` both derive coverage from it.
DIT_LAYER_KINDS = ("adaln", "attn_qkv", "attn_out", "mlp")


def covered_kinds(mixer: str, ffn: str) -> tuple[str, ...]:
    """Which plan layer kinds apply to a (mixer, ffn) block spec.

    The single source of truth for plan coverage: ``apply_plan`` (what
    gets quantized), ``QuantPlan.layer_table`` (reporting), and the
    simulator bridge (what gets costed at INT8) all derive from it.
    SSM/xLSTM mixers are not covered — their projections stay bf16
    until the kernels learn them (ROADMAP R2).
    """
    kinds: list[str] = []
    if mixer in ("attn", "attn_local"):
        kinds += ["attn_qkv", "attn_out", "attn_kv"]
    elif mixer == "mla":
        kinds += ["mla_proj", "mla_out", "attn_kv"]
    if ffn == "dense":
        kinds += ["mlp"]
    elif ffn == "moe":
        # routed experts AND the shared expert ride on moe_experts
        kinds += ["moe_experts"]
    return tuple(kinds)


@dataclass(frozen=True)
class QuantPlan:
    """Per-logical-layer-kind INT8 coverage declaration.

    The default is the paper's configuration: everything on the CIM
    pipeline.  Field order matches :data:`LAYER_KINDS`.
    """

    mlp: bool = True
    attn_qkv: bool = True
    attn_out: bool = True
    attn_kv: bool = True
    moe_experts: bool = True
    adaln: bool = True
    mla_proj: bool = True
    mla_out: bool = True

    # -- constructors ----------------------------------------------------
    @classmethod
    def full(cls) -> "QuantPlan":
        """Every weight matmul on the fused INT8 pipeline (paper §IV-B)."""
        return cls()

    @classmethod
    def none(cls) -> "QuantPlan":
        """bf16 everywhere (the baseline/digital configuration)."""
        return cls(**{k: False for k in LAYER_KINDS})

    @classmethod
    def mlp_only(cls) -> "QuantPlan":
        """Only dense-FFN MLPs quantized."""
        return cls(mlp=True, attn_qkv=False, attn_out=False,
                   attn_kv=False, moe_experts=False, adaln=False,
                   mla_proj=False, mla_out=False)

    # -- queries ---------------------------------------------------------
    def covers(self, kind: str) -> bool:
        if kind not in LAYER_KINDS:
            raise ValueError(f"unknown layer kind {kind!r}; "
                             f"options: {LAYER_KINDS}")
        return bool(getattr(self, kind))

    def layer_table(self, groups) -> list[dict]:
        """Per-scan-group view of what the plan puts on the fused path.

        ``groups``: ``Model.groups`` — [((mixer, ffn), count), ...].
        Returns one row per group: which applicable layer kinds run the
        fused INT8 pipeline there (empty list = bf16 group).
        """
        rows = []
        for gi, (spec, count) in enumerate(groups):
            mixer, ffn = spec
            rows.append({
                "group": gi, "mixer": mixer, "ffn": ffn, "layers": count,
                "fused": [k for k in covered_kinds(mixer, ffn)
                          if self.covers(k)],
            })
        return rows

    def describe(self, groups) -> str:
        """Human-readable plan summary (one line per scan group)."""
        lines = []
        for row in self.layer_table(groups):
            fused = ",".join(row["fused"]) or "-"
            lines.append(f"group_{row['group']} ({row['mixer']}+{row['ffn']}"
                         f" x{row['layers']}): int8[{fused}]")
        return "\n".join(lines)


FULL_INT8 = QuantPlan.full()


# ---------------------------------------------------------------------------
# Param-tree rewrite
# ---------------------------------------------------------------------------
def apply_plan(groups, params, plan: QuantPlan):
    """Rewrite a model's (stacked, scanned) param values tree so every
    plan-covered layer holds QuantizedLinear leaves.

    ``groups``: ``Model.groups``; ``params``: the value tree from
    ``Model.init`` — each ``group_{i}`` entry holds leaves stacked over
    the scan (layers) axis, so per-layer quantization vmaps over it.
    Uncovered layers (and non-matmul leaves: norms, router, rope) pass
    through untouched.  Idempotent: already-quantized leaves are kept.
    """
    out = dict(params)
    for gi, (spec, _count) in enumerate(groups):
        mixer, ffn = spec
        kinds = [k for k in covered_kinds(mixer, ffn) if plan.covers(k)]
        key = f"group_{gi}"
        if key not in out or not kinds:
            continue
        group = dict(out[key])
        if ({"attn_qkv", "attn_out"} & set(kinds)) and "attn" in group:
            group["attn"] = jax.vmap(
                lambda p: quantize_attention(p, qkv="attn_qkv" in kinds,
                                             out="attn_out" in kinds)
            )(group["attn"])
        if ({"mla_proj", "mla_out"} & set(kinds)) and "mla" in group:
            group["mla"] = jax.vmap(
                lambda p: quantize_mla(p, proj="mla_proj" in kinds,
                                       out="mla_out" in kinds)
            )(group["mla"])
        if "mlp" in kinds and "mlp" in group:
            group["mlp"] = jax.vmap(quantize_mlp)(group["mlp"])
        if "moe_experts" in kinds and "moe" in group:
            group["moe"] = jax.vmap(quantize_moe_experts)(group["moe"])
        out[key] = group
    return out


def q_scale_axes(axes: tuple, n_out: int = 1) -> "QuantizedLinear":
    """QuantizedLinear logical axes from a weight's logical axes.

    ``q`` keeps the weight's axes; ``scale`` co-shards with q on the
    output-channel axes (the trailing ``n_out``) — the single
    input-channel axis just before them is dropped, leading structure
    axes (layers/expert) kept — so a mesh resolution that shards q's
    output channels shards the scale identically, which the
    column-parallel fused pipeline requires.
    """
    return QuantizedLinear(q=axes, scale=axes[:-n_out - 1] + axes[-n_out:])


_q_scale_axes = q_scale_axes     # pre-PR-5 internal name


def attn_plan_axes(attn: dict, qkv: bool = True, out: bool = True) -> dict:
    """Logical-axes rewrite for one attention layer's projection leaves
    (the axes mirror of :func:`~repro.quant.linear.quantize_attention`);
    shared by LLM ``plan_axes`` and the DiT model's mesh placement."""
    attn = dict(attn)
    if qkv and "q" in attn:
        qa = attn.pop("q")          # [*, d, H, Dh] head-structured
        attn.pop("k"), attn.pop("v")
        # wide qkv [*, d, H+2KH, Dh]: q's axes cover the
        # concatenated head axis; scale [*, H+2KH, Dh]
        attn["qkv"] = q_scale_axes(qa, n_out=2)
    if out and "o" in attn:
        # o [*, H, Dh, d]: two input-channel axes (H, Dh) fold
        # into the row-parallel shard dim; scale [*, d]
        oa = attn["o"]
        attn["o"] = QuantizedLinear(q=oa, scale=oa[:-3] + oa[-1:])
    return attn


def mla_plan_axes(mla: dict, proj: bool = True, out: bool = True) -> dict:
    """Logical-axes rewrite for one MLA layer (the axes mirror of
    :func:`~repro.quant.linear.quantize_mla`)."""
    mla = dict(mla)
    if proj and "q_down" in mla:
        qa = mla.pop("q_down")              # [*, d, q_lora]
        mla.pop("kv_down")
        mla["down"] = q_scale_axes(qa)
        ua = mla["q_up"]                    # [*, q_lora, H, qk] -> flat
        mla["q_up"] = q_scale_axes(ua[:-1])
    if out and "o" in mla:
        oa = mla["o"]
        mla["o"] = QuantizedLinear(q=oa, scale=oa[:-3] + oa[-1:])
    return mla


def mlp_plan_axes(mlp: dict) -> dict:
    """Logical-axes rewrite for one (dense or DiT) MLP's weight leaves."""
    return {k: q_scale_axes(a) if k in ("up", "down", "gate") else a
            for k, a in mlp.items()}


def plan_axes(groups, axes, plan: QuantPlan):
    """Rewrite a model's logical-axes tree to match the param tree
    :func:`apply_plan` produces: every plan-covered weight leaf becomes
    a :class:`QuantizedLinear` of (q axes, scale axes), with the scale
    co-sharded on the output-channel axes.

    ``axes``: ``Model.param_axes()`` (stacked groups carry a leading
    "layers" axis).  Resolving the result against a model-axis mesh via
    ``parallel.sharding.make_shardings`` yields the tensor-parallel
    weight placement: QKV/up/gate sharded on output channels, out-proj/
    down on input channels, MoE stacks on the expert axis — with each
    q's scale sharded alongside it.
    """
    out = dict(axes)
    for gi, (spec, _count) in enumerate(groups):
        mixer, ffn = spec
        kinds = [k for k in covered_kinds(mixer, ffn) if plan.covers(k)]
        key = f"group_{gi}"
        if key not in out or not kinds:
            continue
        group = dict(out[key])
        if ({"attn_qkv", "attn_out"} & set(kinds)) and "attn" in group:
            group["attn"] = attn_plan_axes(group["attn"],
                                           qkv="attn_qkv" in kinds,
                                           out="attn_out" in kinds)
        if ({"mla_proj", "mla_out"} & set(kinds)) and "mla" in group:
            group["mla"] = mla_plan_axes(group["mla"],
                                         proj="mla_proj" in kinds,
                                         out="mla_out" in kinds)
        if "mlp" in kinds and "mlp" in group:
            group["mlp"] = mlp_plan_axes(group["mlp"])
        if "moe_experts" in kinds and "moe" in group:
            moe = dict(group["moe"])
            for k in ("up", "down", "gate"):
                if k in moe:
                    moe[k] = q_scale_axes(moe[k])
            if "shared" in moe:
                moe["shared"] = mlp_plan_axes(moe["shared"])
            group["moe"] = moe
        out[key] = group
    return out


def plan_is_applied(groups, params, plan: QuantPlan) -> bool:
    """True if every plan-covered layer already holds QuantizedLinear
    leaves (used by tests and idempotence checks)."""
    for gi, (spec, _count) in enumerate(groups):
        mixer, ffn = spec
        group = params.get(f"group_{gi}", {})
        if mixer in ("attn", "attn_local") and "attn" in group:
            attn = group["attn"]
            if plan.attn_qkv and not isinstance(attn.get("qkv"),
                                                QuantizedLinear):
                return False
            if plan.attn_out and not isinstance(attn.get("o"),
                                                QuantizedLinear):
                return False
        if mixer == "mla" and "mla" in group:
            mla = group["mla"]
            if plan.mla_proj and not isinstance(mla.get("down"),
                                                QuantizedLinear):
                return False
            if plan.mla_out and not isinstance(mla.get("o"),
                                               QuantizedLinear):
                return False
        if ffn == "dense" and plan.mlp and "mlp" in group:
            if not isinstance(group["mlp"].get("up"), QuantizedLinear):
                return False
        if ffn == "moe" and plan.moe_experts and "moe" in group:
            if not isinstance(group["moe"].get("up"), QuantizedLinear):
                return False
    return True
