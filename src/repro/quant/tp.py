"""Tensor-parallel execution of the fused INT8 pipeline via shard_map.

The paper's CIM-MXU scales by partitioning the weight-stationary arrays
over macros and chips; this module is the software mirror for the fused
Pallas pipeline: every device holds one shard of the int8 weights (and
their co-sharded scales) and runs the *same* fused kernels on its slice,
with the minimal collectives the partition implies:

    column-parallel (QKV, MLP up/gate)
        Weights sharded on the output-channel axis; activations are
        replicated, so each shard's per-column math — in-kernel row
        quantization included — is exactly the unsharded pipeline's.
        No collective at all; the output is logically sharded on its
        last axis.

    row-parallel (attention out-projection, MLP down)
        Weights sharded on the input-channel axis.  Three exactness
        rules keep the result bit-identical to the unsharded pipeline:
        (1) the activation row absmax is pmax'd across shards before
        quantizing, so every shard uses the *global* row scale;
        (2) the int32 partial accumulators are psum'd — integer
        addition is exact, so the summed accumulator equals the
        unsharded one bit-for-bit; (3) the dequant/residual epilogue
        runs ONCE on the summed accumulator (a per-shard epilogue would
        distribute the f32 rescale over the sum and change roundings).
        The psum therefore folds in *before* the residual epilogue.

    expert-parallel (grouped MoE pipeline)
        The stacked capacity buffers, weights, scales, and the
        zero-capacity skip list shard on the leading expert axis; each
        device runs the constant-3-dispatch grouped pipeline on its
        E/p experts.  The expert axis is batch-like, so this is
        trivially exact.

Per-shard Pallas dispatch counts are unchanged from the unsharded
pipeline (5 per dense decode block, 8 per MoE block — structurally
pinned in tests/test_tp.py).

Activation: a :func:`repro.parallel.context.sharding_context` whose mesh
has a ``model`` axis (the axis the `mlp`/`heads`/`expert` logical rules
bind) turns these paths on inside ``quantized_qkv_proj`` /
``quantized_out_proj`` / ``quantized_mlp_apply`` / ``quantized_moe_apply``
— no call-site flags, same as kernel dispatch on QuantizedLinear leaves.
Dimensions that the model-axis size does not divide fall back to the
unsharded path (the same replicate-on-indivisible rule as
``parallel.sharding.resolve_spec``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops as kops
from repro.kernels import ref as kref

# The mesh axis the fused pipeline shards over — the same axis the
# "mlp"/"heads"/"expert" logical rules bind in parallel.sharding.
TP_AXIS = "model"


def tp_mesh() -> Mesh | None:
    """The active mesh when a sharding context with a model axis is live.

    Returns None outside a context or when the mesh has no ``model``
    axis; a 1-sized model axis still returns the mesh (the shard_map
    path is exercised with trivial shards — 1-way == unsharded is part
    of the parity contract).
    """
    from repro.parallel.context import current_context
    ctx = current_context()
    if ctx is None:
        return None
    mesh, _rules = ctx
    if TP_AXIS not in mesh.shape:
        return None
    return mesh


def shards(mesh: Mesh) -> int:
    return mesh.shape[TP_AXIS]


def _global_rowquant(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Row absmax int8 quantization with the absmax pmax'd over the TP
    axis: every shard quantizes its input-channel slice with the global
    row scale, so ``q`` is the unsharded quantization's slice
    bit-for-bit (max is exact; the scalar chain matches
    ``quantize_rows_int8`` / its oracle)."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    amax = jax.lax.pmax(amax, TP_AXIS) + 1e-12
    scale = amax * (1.0 / 127.0)
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def matmul_column(mesh: Mesh, x2: jax.Array, w_q: jax.Array,
                  w_scale: jax.Array, use_kernel: bool,
                  activation: str | None = None) -> jax.Array:
    """Column-parallel fused matmul: x2 [M, K] replicated, w_q [K, N]
    sharded on N (scale co-sharded) -> [M, N] sharded on N."""
    def body(xl, wl, sl):
        if use_kernel:
            return kops.cim_quantized_matmul_fused(xl, wl, sl,
                                                   activation=activation)
        return kref.fused_matmul_ref(xl, wl, sl, activation=activation)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), P(None, TP_AXIS), P(TP_AXIS)),
                         out_specs=P(None, TP_AXIS), check_vma=False)(
                         x2, w_q, w_scale)


def matmul_row(mesh: Mesh, x2: jax.Array, w_q: jax.Array,
               w_scale: jax.Array, use_kernel: bool,
               residual: jax.Array | None = None) -> jax.Array:
    """Row-parallel fused matmul: x2 [M, K] sharded on K, w_q [K, N]
    sharded on K -> [M, N] replicated; the int32 psum folds in before
    the dequant/residual epilogue (see module docstring)."""
    def body(xl, wl, sl, *rest):
        x_q, x_s = _global_rowquant(xl)
        acc = (kops.cim_int8_gemm_acc(x_q, wl) if use_kernel
               else kref.cim_gemm_int8_ref(x_q, wl))
        acc = jax.lax.psum(acc, TP_AXIS)
        out = acc.astype(jnp.float32) * x_s * sl[None, :]
        if rest:
            out = out + rest[0].astype(jnp.float32)
        return out

    in_specs = [P(None, TP_AXIS), P(TP_AXIS, None), P()]
    args = [x2, w_q, w_scale]
    if residual is not None:
        in_specs.append(P())
        args.append(residual)
    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=P(), check_vma=False)(*args)


def mlp(mesh: Mesh, x2: jax.Array, qparams: dict, activation: str,
        use_kernel: bool, residual: jax.Array | None = None) -> jax.Array:
    """The whole fused MLP pipeline, tensor-parallel in one shard_map:
    up/gate column-parallel, hidden requant with a pmax'd global row
    scale, down row-parallel with the int32 psum folded in before the
    residual epilogue.  x2 [M, d] replicated -> [M, d] replicated, f32.
    """
    gate = qparams.get("gate")

    def body(xl, uq, us, dq, ds, *rest):
        rest = list(rest)
        gq = gs = None
        if gate is not None:
            gq, gs = rest.pop(0), rest.pop(0)
        rl = rest.pop(0) if rest else None
        if use_kernel:
            x_q, x_s = kops.quantize_rows_int8(xl)
            h = kops.cim_hidden_int8(x_q, x_s, uq, us, gq, gs,
                                     activation=activation)
        elif gq is not None:
            h = kref.gated_mlp_hidden_ref(xl, gq, gs, uq, us, activation)
        else:
            h = kref.fused_matmul_ref(xl, uq, us, activation=activation)
        h_q, h_s = _global_rowquant(h)
        acc = (kops.cim_int8_gemm_acc(h_q, dq) if use_kernel
               else kref.cim_gemm_int8_ref(h_q, dq))
        acc = jax.lax.psum(acc, TP_AXIS)
        out = acc.astype(jnp.float32) * h_s * ds[None, :]
        if rl is not None:
            out = out + rl.astype(jnp.float32)
        return out

    in_specs = [P(), P(None, TP_AXIS), P(TP_AXIS), P(TP_AXIS, None), P()]
    args = [x2, qparams["up"].q, qparams["up"].scale,
            qparams["down"].q, qparams["down"].scale]
    if gate is not None:
        in_specs += [P(None, TP_AXIS), P(TP_AXIS)]
        args += [gate.q, gate.scale]
    if residual is not None:
        in_specs.append(P())
        args.append(residual)
    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=P(), check_vma=False)(*args)


def grouped_moe(mesh: Mesh, x: jax.Array, qparams: dict, activation: str,
                use_kernel: bool,
                expert_counts: jax.Array | None = None) -> jax.Array:
    """Expert-parallel grouped MoE pipeline: the stacked [E, T, d]
    capacity buffers, [E, K, N] weight stacks, and the zero-capacity
    skip list all shard on the expert axis; every device runs the
    constant-3-dispatch grouped pipeline on its E/p experts."""
    gate = qparams.get("gate")

    def body(xl, uq, us, dq, ds, *rest):
        rest = list(rest)
        gq = gs = None
        if gate is not None:
            gq, gs = rest.pop(0), rest.pop(0)
        cl = rest.pop(0) if rest else None
        if use_kernel:
            return kops.cim_quantized_grouped_mlp(
                xl, uq, us, dq, ds, gate_q=gq, gate_scale=gs,
                expert_counts=cl, activation=activation)
        qtree = {"up": (uq, us), "down": (dq, ds)}
        if gq is not None:
            qtree["gate"] = (gq, gs)
        return kref.grouped_quantized_mlp_ref(xl, qtree, activation)

    espec = P(TP_AXIS)
    in_specs = [espec, espec, espec, espec, espec]
    args = [x, qparams["up"].q, qparams["up"].scale,
            qparams["down"].q, qparams["down"].scale]
    if gate is not None:
        in_specs += [espec, espec]
        args += [gate.q, gate.scale]
    if expert_counts is not None:
        in_specs.append(espec)
        args.append(expert_counts)
    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=espec, check_vma=False)(*args)


def decode_attn(mesh: Mesh, q: jax.Array, k: jax.Array, v: jax.Array,
                pos: jax.Array, q_pos: jax.Array,
                k_scale: jax.Array | None = None,
                v_scale: jax.Array | None = None, *,
                window: int | None = None,
                use_kernel: bool = True) -> jax.Array:
    """Head-parallel flash-decode over a KV cache sharded on KV heads.

    q [B, KH, G, D] and k/v [B, S, KH, D] (+[B, S, KH] scales on the
    int8 path) shard on their KV-head axis; pos/q_pos replicate.  Every
    head's softmax is independent, so each shard runs the *same* decode
    kernel (or its interpret oracle) on its KH/p heads with no
    collective at all — the per-shard KV-cache residency drops to
    1/p of the replicated cache, which is the point: decode attention
    is memory-bound and the cache is the memory.
    """
    def body(ql, kl, vl, posl, qpl, *sc):
        ks, vs = sc if sc else (None, None)
        if use_kernel:
            return kops.decode_attention(ql, kl, vl, posl, qpl,
                                         k_scale=ks, v_scale=vs,
                                         window=window)
        return kref.decode_attention_ref(ql, kl, vl, posl, qpl,
                                         window=window, k_scale=ks,
                                         v_scale=vs)

    in_specs = [P(None, TP_AXIS), P(None, None, TP_AXIS),
                P(None, None, TP_AXIS), P(), P()]
    args = [q, k, v, pos, q_pos]
    if k_scale is not None:
        in_specs += [P(None, None, TP_AXIS), P(None, None, TP_AXIS)]
        args += [k_scale, v_scale]
    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=P(None, TP_AXIS), check_vma=False)(*args)


def decode_attn_paged(mesh: Mesh, q: jax.Array, k_pages: jax.Array,
                      v_pages: jax.Array, pos_pages: jax.Array,
                      block_tables: jax.Array, q_pos: jax.Array,
                      layer: jax.Array,
                      k_scale_pages: jax.Array | None = None,
                      v_scale_pages: jax.Array | None = None, *,
                      window: int | None = None,
                      use_kernel: bool = True) -> jax.Array:
    """Head-parallel paged flash-decode: the block-table analogue of
    :func:`decode_attn`.

    q [B, KH, G, D] and the stacked KV block pools [L, NB, bs, KH, D]
    (+[L, NB, bs, KH] scales on the int8 path) shard on their KV-head
    axis; the block tables, position pages and the layer index replicate
    (they are head-agnostic metadata).  Each shard streams its KH/p heads
    of pool layer ``layer`` through the same scalar-prefetched
    block-table kernel with no collective — the paged pool, like the
    ring cache, holds 1/p of the KV bytes per device.
    """
    def body(ql, kl, vl, posl, btl, qpl, ly, *sc):
        ks, vs = sc if sc else (None, None)
        if use_kernel:
            return kops.decode_attention_paged(ql, kl, vl, posl, btl, qpl,
                                               ly, k_scale_pages=ks,
                                               v_scale_pages=vs,
                                               window=window)
        return kref.decode_attention_paged_ref(ql, kl, vl, posl, btl, qpl,
                                               ly, window=window,
                                               k_scale_pages=ks,
                                               v_scale_pages=vs)

    pool = P(None, None, None, TP_AXIS)
    in_specs = [P(None, TP_AXIS), pool, pool, P(), P(), P(), P()]
    args = [q, k_pages, v_pages, pos_pages, block_tables, q_pos,
            jnp.asarray(layer, jnp.int32)]
    if k_scale_pages is not None:
        in_specs += [pool, pool]
        args += [k_scale_pages, v_scale_pages]
    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=P(None, TP_AXIS), check_vma=False)(*args)
