"""INT8 weight quantization for serving — the paper's INT8 CIM mode,
end to end on the Pallas `cim_gemm` kernels.

The paper evaluates all workloads at INT8 ("using INT8 data precision",
§IV-B): weights live in the CIM arrays as int8, activations are
quantized by the pre-processing unit, and the post-processing unit
rescales — all *inside* the MXU pipeline, nothing round-trips to HBM
between the stages.  This module is the software mirror: per-output-
channel int8 weights + dynamic per-row activation quantization + f32
rescale/bias/activation, dispatched to the **fused** Pallas pipeline
(``kernels.ops.cim_quantized_matmul_fused`` / ``cim_quantized_mlp``)
when ``use_kernel`` is set, or to the matching jnp oracle otherwise.

Which layers run this path is declared by a :class:`~repro.quant.plan.
QuantPlan` (plan.py) covering the four logical layer kinds the CIM-MXU
serves: dense-FFN MLPs, attention QKV (one wide fused GEMM), the
attention out-projection (residual add fused into the epilogue), and
MoE expert MLPs (ONE grouped pipeline over the stacked per-expert
capacity buffers — dispatch count independent of the expert count).
``use_kernel=None`` auto-selects: fused kernels on TPU, the
identical-math oracle on CPU (overridable with :func:`kernel_mode`).

Under an active :func:`~repro.parallel.context.sharding_context` whose
mesh has a ``model`` axis, the four apply sites additionally go
tensor-parallel (quant/tp.py): QKV/up/gate column-parallel, out-proj/
down row-parallel with the int32 psum folded in before the residual
epilogue, MoE expert-parallel — bit-identical to the unsharded path,
with per-shard dispatch counts unchanged.  Dims the model axis does not
divide fall back to the unsharded path (replicate-on-indivisible, the
same rule parallel.sharding uses).

Validated against the bf16 references in tests/test_quant.py.
"""
from __future__ import annotations

import contextlib
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from . import tp as _tp


class QuantizedLinear(NamedTuple):
    """Per-output-channel symmetric int8 weight.

    ``q`` may carry extra structure axes (e.g. [in, heads, head_dim] for
    the fused QKV projection, [heads, head_dim, out] for the attention
    out-projection, [experts, in, out] for MoE experts); ``scale``
    matches the output-channel axes.  Apply sites flatten to 2D.
    """

    q: jax.Array        # int8 [in, out] (or structured, see above)
    scale: jax.Array    # f32 [out]


# ---------------------------------------------------------------------------
# Kernel-dispatch resolution
# ---------------------------------------------------------------------------
_KERNEL_MODE: bool | None = None


@contextlib.contextmanager
def kernel_mode(force: bool | None):
    """Force ``use_kernel=None`` call sites to the Pallas pipeline (True)
    or the jnp oracle (False) for the enclosed scope — lets model-level
    entry points (block_apply, the serving engine) be traced on the
    kernel path from CPU tests without threading a flag through every
    layer."""
    global _KERNEL_MODE
    prev = _KERNEL_MODE
    _KERNEL_MODE = force
    try:
        yield
    finally:
        _KERNEL_MODE = prev


def _resolve_use_kernel(use_kernel: bool | None) -> bool:
    if use_kernel is None:
        if _KERNEL_MODE is not None:
            return _KERNEL_MODE
        return jax.default_backend() != "cpu"
    return use_kernel


# ---------------------------------------------------------------------------
# Degraded-mode execution (reliability layer)
# ---------------------------------------------------------------------------
_DEGRADED_MODE: bool = False


@contextlib.contextmanager
def degraded_mode(enable: bool = True):
    """Per-layer degraded-mode fallback for the enclosed scope.

    When enabled, every quantized apply site folds a cheap
    ``jnp.isfinite`` reduction over its fused-pipeline output and — only
    on the step where that screen trips — re-runs the layer on the
    unquantized reference path with non-finite inputs/scales sanitized
    to zero (``lax.cond``: exactly one branch executes at runtime, so
    the healthy path pays one reduction, not a second GEMM).  The
    contract: a degraded layer's output is always finite; corrupted
    channels contribute zero instead of poisoning the residual stream.

    Default off — the jaxpr (and hence the pinned per-block dispatch
    counts) is unchanged unless a reliability-aware caller (the serving
    engines' ``degraded=True``) opts in at trace time.
    """
    global _DEGRADED_MODE
    prev = _DEGRADED_MODE
    _DEGRADED_MODE = enable
    try:
        yield
    finally:
        _DEGRADED_MODE = prev


def _san(a):
    """Sanitize a float operand for the degraded fallback (int8 weights
    are always finite; scales/activations/bias/residual may not be)."""
    return None if a is None else jnp.nan_to_num(
        a, nan=0.0, posinf=0.0, neginf=0.0)


def _screen(out: jax.Array, fallback) -> jax.Array:
    """Finite screen + reference fallback when degraded mode is active."""
    if not _DEGRADED_MODE:
        return out
    return jax.lax.cond(jnp.isfinite(out).all(), lambda: out, fallback)


def _tp_mesh_for(*dims: int):
    """The active TP mesh when every ``dim`` divides the model-axis
    size; None otherwise (fall back to the unsharded path — the same
    replicate-on-indivisible rule as parallel.sharding)."""
    mesh = _tp.tp_mesh()
    if mesh is None:
        return None
    p = _tp.shards(mesh)
    if any(d % p for d in dims):
        return None
    return mesh


def _canon_activation(activation: str | None) -> str | None:
    if activation in ("gelu", "geglu"):
        return "gelu"
    if activation in ("silu", "swiglu"):
        return "silu"
    return activation


def quantize_linear(w: jax.Array) -> QuantizedLinear:
    q, s = kops.quantize_weights_int8(w.astype(jnp.float32))
    return QuantizedLinear(q, s)


def quantized_matmul(x: jax.Array, w: QuantizedLinear,
                     use_kernel: bool | None = False,
                     bias: jax.Array | None = None,
                     residual: jax.Array | None = None,
                     activation: str | None = None) -> jax.Array:
    """x [..., K] @ int8 W (+ bias, + activation, + residual) -> f32.

    use_kernel=True dispatches the fused Pallas pipeline — a single
    GEMM dispatch with in-kernel activation quantization when K fits
    the VMEM row budget, quantize + fused GEMM otherwise (interpret
    mode on CPU — same integer math, slower); False uses the jnp oracle
    (identical numerics, fast on CPU); None picks the kernel exactly
    when running on a TPU backend (or per :func:`kernel_mode`).
    ``residual [..., N]`` is added after the activation inside the
    epilogue (the transformer-block skip connection).
    """
    use_kernel = _resolve_use_kernel(use_kernel)
    activation = _canon_activation(activation)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    r2 = None if residual is None else residual.reshape(-1,
                                                        residual.shape[-1])
    if use_kernel:
        out = kops.cim_quantized_matmul_fused(x2, w.q, w.scale, bias=bias,
                                              residual=r2,
                                              activation=activation)
    else:
        out = kref.fused_matmul_ref(x2, w.q, w.scale, bias=bias,
                                    residual=r2, activation=activation)
    out = _screen(out, lambda: kref.fused_matmul_ref(
        _san(x2), w.q, _san(w.scale), bias=_san(bias), residual=_san(r2),
        activation=activation))
    return out.reshape(*lead, -1)


# ---------------------------------------------------------------------------
# MLP-block quantization (the dominant decode weight traffic)
# ---------------------------------------------------------------------------
def quantize_mlp(mlp_params: dict) -> dict:
    """{'up','down'[,'gate']} bf16 -> QuantizedLinear tree.  Idempotent:
    already-quantized leaves pass through."""
    out = {k: v if isinstance(v, QuantizedLinear) else quantize_linear(v)
           for k, v in mlp_params.items() if k in ("up", "down", "gate")}
    return out


def quantized_mlp_apply(qparams: dict, x: jax.Array, activation: str,
                        use_kernel: bool | None = False,
                        residual: jax.Array | None = None) -> jax.Array:
    """Quantized MLP block on the fused INT8 pipeline.

    use_kernel=True: one quantize kernel + two fused GEMM kernels per
    gated MLP (the gated front half computes ``act(gate) * up`` and
    re-quantizes the hidden state in its epilogue; the down GEMM
    consumes int8 directly and adds ``residual`` — the block skip
    connection — in its own epilogue).  Non-gated MLPs fuse the
    activation into the up GEMM's epilogue instead.  use_kernel=False
    runs the jnp oracle with identical numerics; None auto-selects by
    backend.
    """
    use_kernel = _resolve_use_kernel(use_kernel)
    act = _canon_activation(activation)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    r2 = None if residual is None else residual.reshape(-1,
                                                        residual.shape[-1])
    mesh = _tp_mesh_for(qparams["up"].q.shape[1])
    if mesh is not None:
        # Tensor-parallel: up/gate column-parallel, down row-parallel
        # with the int32 psum folded in before the residual epilogue
        # (bit-identical to the unsharded pipeline, see quant/tp.py).
        out = _tp.mlp(mesh, x2, qparams, act, use_kernel, residual=r2)
    elif use_kernel:
        gate = qparams.get("gate")
        out = kops.cim_quantized_mlp(
            x2, qparams["up"].q, qparams["up"].scale,
            qparams["down"].q, qparams["down"].scale,
            gate_q=None if gate is None else gate.q,
            gate_scale=None if gate is None else gate.scale,
            residual=r2, activation=act)
    else:
        qtree = {k: (v.q, v.scale) for k, v in qparams.items()}
        out = kref.quantized_mlp_ref(x2, qtree, act, residual=r2)
    out = _screen(out, lambda: kref.quantized_mlp_ref(
        _san(x2), {k: (v.q, _san(v.scale)) for k, v in qparams.items()
                   if k in ("up", "gate", "down")}, act, residual=_san(r2)))
    return out.reshape(*lead, -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention projections (fused QKV + out-projection w/ residual epilogue)
# ---------------------------------------------------------------------------
def quantize_attention(attn_params: dict, qkv: bool = True,
                       out: bool = True) -> dict:
    """Quantize one attention layer's projection weights.

    ``q [d, H, Dh]``, ``k``/``v [d, KH, Dh]`` fuse into a single
    ``"qkv"`` :class:`QuantizedLinear` with ``q`` int8 of shape
    [d, H + 2*KH, Dh] (heads concatenated along the output axis — one
    wide weight-stationary GEMM per step) and per-channel ``scale``
    [H + 2*KH, Dh].  ``o [H, Dh, d]`` keeps its head structure in the
    int8 tensor (scale [d]).  Norm/rope leaves pass through unchanged.
    """
    p = dict(attn_params)
    if qkv and "q" in p and not isinstance(p.get("q"), QuantizedLinear):
        wq, wk, wv = p.pop("q"), p.pop("k"), p.pop("v")
        wide = jnp.concatenate([wq, wk, wv], axis=-2)   # [d, H+2KH, Dh]
        d = wide.shape[0]
        flat = quantize_linear(wide.reshape(d, -1))
        p["qkv"] = QuantizedLinear(flat.q.reshape(wide.shape),
                                   flat.scale.reshape(wide.shape[1:]))
    if out and "o" in p and not isinstance(p.get("o"), QuantizedLinear):
        wo = p["o"]                                     # [H, Dh, d]
        flat = quantize_linear(wo.reshape(-1, wo.shape[-1]))
        p["o"] = QuantizedLinear(flat.q.reshape(wo.shape), flat.scale)
    return p


def quantize_mla(mla_params: dict, proj: bool = True,
                 out: bool = True) -> dict:
    """Quantize one MLA layer's projections.

    ``q_down [d, q_lora]`` and ``kv_down [d, kv_lora + rope]`` take the
    same input, so they fuse into one ``"down"`` :class:`QuantizedLinear`
    ([d, q_lora + kv_lora + rope] int8, one wide GEMM, split after);
    ``q_up [q_lora, H, nope + rope]`` is stored flat ([q_lora, H * (nope
    + rope)], the layout its GEMM reads, so no layer relays it out);
    ``o [H, v, d]`` as the attention out-projection.
    ``kv_up`` (W_UK / W_UV), the norms and anything already quantized
    pass through.
    """
    p = dict(mla_params)
    if proj and "q_down" in p:
        wide = jnp.concatenate([p.pop("q_down"), p.pop("kv_down")], axis=-1)
        p["down"] = quantize_linear(wide)
        wq = p["q_up"]
        p["q_up"] = quantize_linear(wq.reshape(wq.shape[0], -1))
    if out and "o" in p and not isinstance(p.get("o"), QuantizedLinear):
        wo = p["o"]
        flat = quantize_linear(wo.reshape(-1, wo.shape[-1]))
        p["o"] = QuantizedLinear(flat.q.reshape(wo.shape), flat.scale)
    return p


def quantized_qkv_proj(qkv: QuantizedLinear, x: jax.Array,
                       use_kernel: bool | None = None) -> jax.Array:
    """One wide fused GEMM for all of q/k/v: x [..., d] -> [..., HK, Dh].

    The concatenated output axis means a single quantize-in-kernel
    dispatch feeds all three projections; callers split along the head
    axis afterwards (free — no data movement).  Under a model-axis
    sharding context the wide GEMM runs column-parallel: each shard's
    fused pipeline (quantization included — the activations are
    replicated) is the unsharded per-column math bit-for-bit.
    """
    d, HK, Dh = qkv.q.shape
    flat = QuantizedLinear(qkv.q.reshape(d, HK * Dh),
                           qkv.scale.reshape(HK * Dh))
    # Gate on the HEAD count, not the flattened width: weight placement
    # (plan_axes -> resolve_spec) shards the structured head axis, and
    # HK % p keeps the flattened contiguous chunks whole-head-aligned —
    # the same layout device_put placed, so no per-step resharding.
    mesh = _tp_mesh_for(HK)
    if mesh is not None:
        lead = x.shape[:-1]
        wide = _tp.matmul_column(mesh, x.reshape(-1, d), flat.q, flat.scale,
                                 _resolve_use_kernel(use_kernel))
        wide = _screen(wide, lambda: kref.fused_matmul_ref(
            _san(x.reshape(-1, d)), flat.q, _san(flat.scale)))
        wide = wide.reshape(*lead, -1)
    else:
        wide = quantized_matmul(x, flat, use_kernel=use_kernel)
    return wide.reshape(*x.shape[:-1], HK, Dh)


def quantized_out_proj(o: QuantizedLinear, attn_out: jax.Array,
                       residual: jax.Array | None = None,
                       use_kernel: bool | None = None) -> jax.Array:
    """Attention out-projection with the residual add fused into the
    GEMM epilogue: attn_out [..., H, Dh] -> [..., d].

    Under a model-axis sharding context the projection runs
    row-parallel: the input-channel (head) axis is sharded, each shard
    quantizes its slice with the pmax'd global row scale, and the int32
    partial accumulators psum before the one dequant/residual epilogue
    — bit-identical to the unsharded pipeline.
    """
    H, Dh, d = o.q.shape
    flat = QuantizedLinear(o.q.reshape(H * Dh, d), o.scale)
    x2 = attn_out.reshape(*attn_out.shape[:-2], H * Dh)
    # Gate on the head count H — the axis weight placement shards (o's
    # "heads" logical axis) — so compute sharding matches placement.
    mesh = _tp_mesh_for(H)
    if mesh is not None:
        lead = x2.shape[:-1]
        r2 = None if residual is None else residual.reshape(-1, d)
        out = _tp.matmul_row(mesh, x2.reshape(-1, H * Dh), flat.q,
                             flat.scale, _resolve_use_kernel(use_kernel),
                             residual=r2)
        out = _screen(out, lambda: kref.fused_matmul_ref(
            _san(x2.reshape(-1, H * Dh)), flat.q, _san(flat.scale),
            residual=_san(r2)))
        return out.reshape(*lead, d)
    return quantized_matmul(x2, flat, use_kernel=use_kernel,
                            residual=residual)


# ---------------------------------------------------------------------------
# MoE expert MLPs (grouped-expert fused pipeline, one kernel for all E)
# ---------------------------------------------------------------------------
def quantize_moe_experts(moe_params: dict) -> dict:
    """Quantize one MoE layer: routed expert weights [E, K, N] become
    per-expert QuantizedLinear stacks (q int8 [E, K, N], scale [E, N]);
    the shared-expert MLP is quantized like a dense MLP.  The router
    stays f32 (negligible FLOPs, routing decisions are
    precision-sensitive)."""
    out = dict(moe_params)
    for name in ("up", "gate", "down"):
        if name in out and not isinstance(out[name], QuantizedLinear):
            q, s = jax.vmap(kops.quantize_weights_int8)(
                out[name].astype(jnp.float32))
            out[name] = QuantizedLinear(q, s)
    if "shared" in out and not isinstance(out["shared"].get("up"),
                                          QuantizedLinear):
        out["shared"] = quantize_mlp(out["shared"])
    return out


def quantized_moe_apply(qparams: dict, x: jax.Array, activation: str,
                        use_kernel: bool | None = False,
                        expert_counts: jax.Array | None = None,
                        groups: jax.Array | None = None) -> jax.Array:
    """Grouped-expert fused INT8 MLPs: x [E, T, d] -> [E, T, d].

    ALL experts' capacity buffers run the fused pipeline in a **constant
    number of Pallas dispatches** — one quantize over the stacked rows,
    one grouped (gated) up GEMM, one grouped down GEMM — with the expert
    index as a kernel grid dimension indexing the stacked int8
    weight/scale tensors (``kernels.ops.cim_quantized_grouped_mlp``).
    The CIM mapping: every expert's weight tile sits in its own macro
    sub-grid and the dispatched tokens stream through simultaneously.
    Dispatch count is independent of E (qwen2-moe's 60 or deepseek-v3's
    256 experts cost the same trace as 4); the per-expert Python loop
    this replaces traced 3·E kernels and is kept as
    :func:`quantized_moe_apply_looped` for parity tests and benches.

    ``expert_counts`` (int32 [E], the router's per-expert token tally)
    is the zero-capacity skip list: experts that received no tokens
    skip their MXU work inside the grouped kernels (scalar-prefetch
    guard) instead of streaming all-zero rows — same dispatches, same
    bits.  Under a model-axis sharding context the pipeline runs
    expert-parallel: every device serves its E/p experts' stacks.

    Ragged form (``groups``, int32 [n_tiles]): x is [n_tiles, tm, d] row
    tiles, tile t runs expert ``groups[t]`` and ``expert_counts`` is per
    tile (0: empty); it runs unsharded.

    use_kernel=False runs the bit-identical grouped jnp oracle; None
    auto-selects by backend (or per :func:`kernel_mode`).
    """
    use_kernel = _resolve_use_kernel(use_kernel)
    act = _canon_activation(activation)
    gate = qparams.get("gate")
    mesh = None if groups is not None else _tp_mesh_for(x.shape[0])
    if mesh is not None:
        out = _tp.grouped_moe(mesh, x, qparams, act, use_kernel,
                              expert_counts=expert_counts)
    elif use_kernel:
        out = kops.cim_quantized_grouped_mlp(
            x, qparams["up"].q, qparams["up"].scale,
            qparams["down"].q, qparams["down"].scale,
            gate_q=None if gate is None else gate.q,
            gate_scale=None if gate is None else gate.scale,
            expert_counts=expert_counts, groups=groups, activation=act)
    else:
        qtree = {k: (v.q, v.scale) for k, v in qparams.items()
                 if k in ("up", "gate", "down")}
        out = kref.grouped_quantized_mlp_ref(x, qtree, act, groups=groups)
    out = _screen(out, lambda: kref.grouped_quantized_mlp_ref(
        _san(x), {k: (v.q, _san(v.scale)) for k, v in qparams.items()
                  if k in ("up", "gate", "down")}, act, groups=groups))
    return out.astype(x.dtype)


def quantized_moe_apply_looped(qparams: dict, x: jax.Array, activation: str,
                               use_kernel: bool | None = False) -> jax.Array:
    """Per-expert loop over the fused dense-MLP pipeline (3·E dispatches).

    The pre-grouped-kernel implementation, retained as the bit-for-bit
    comparator for :func:`quantized_moe_apply` (tests pin grouped ==
    looped exactly) and as the benchmark baseline that shows the
    dispatch-count win.  Not used on any model path.
    """
    use_kernel = _resolve_use_kernel(use_kernel)
    E = x.shape[0]
    names = [k for k in ("up", "gate", "down") if k in qparams]
    outs = []
    for e in range(E):
        qp = {k: QuantizedLinear(qparams[k].q[e], qparams[k].scale[e])
              for k in names}
        outs.append(quantized_mlp_apply(qp, x[e], activation,
                                        use_kernel=use_kernel))
    return jnp.stack(outs)


def dequantize_tree(qtree: dict) -> dict:
    """QuantizedLinear tree -> f32 weights (for parity checks)."""
    return {k: (v.q.astype(jnp.float32) * v.scale[None, :])
            for k, v in qtree.items()}
