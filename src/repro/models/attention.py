"""Attention: GQA/MQA, sliding-window, prefix-LM, blockwise online-softmax.

Large-context paths never materialize the full score matrix: prefill and
training use ``blockwise_attention`` (a pure-JAX flash-attention with the
paper's online-softmax normalizer [27], scanned over KV blocks), which is
also the oracle for the Pallas ``flash_attention`` kernel.  Decode attends
one query step against a fixed-capacity KV cache with length masking.

Shapes: q [B, Sq, H, D]; k/v [B, Skv, KH, D]; GQA groups G = H // KH are
kept factored ([B, Sq, KH, G, D]) so KV is never repeated in memory.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.parallel.context import shard
from repro.quant.linear import (QuantizedLinear, _resolve_use_kernel,
                                _tp_mesh_for, quantized_out_proj,
                                quantized_qkv_proj)
from .layers import Param, apply_rope, linear_param, rmsnorm_apply, scale_param

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def attention_init(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, dtype=jnp.bfloat16,
                   qk_norm: bool = False) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "q": linear_param(kq, d_model, (n_heads, head_dim),
                          ("fsdp", "heads", None), dtype),
        "k": linear_param(kk, d_model, (n_kv_heads, head_dim),
                          ("fsdp", "kv_heads", None), dtype),
        "v": linear_param(kv, d_model, (n_kv_heads, head_dim),
                          ("fsdp", "kv_heads", None), dtype),
        "o": Param(
            linear_param(ko, n_heads * head_dim, (d_model,), (), dtype).value
            .reshape(n_heads, head_dim, d_model),
            ("heads", None, "fsdp")),
    }
    if qk_norm:
        p["q_norm"] = {"scale": scale_param(head_dim)}
        p["k_norm"] = {"scale": scale_param(head_dim)}
    return p


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------
def _mask_bias(q_pos: jax.Array, kv_pos: jax.Array, kind: str,
               window: Optional[int] = None,
               prefix_len: Optional[jax.Array] = None,
               kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Additive bias [..., Sq, Skv]; 0 where attending is allowed."""
    q = q_pos[..., :, None]
    k = kv_pos[..., None, :]
    if kind == "causal":
        ok = k <= q
    elif kind == "sliding":
        ok = (k <= q) & (k > q - window)
    elif kind == "prefix":
        # bidirectional within the prefix, causal elsewhere
        p = jnp.asarray(prefix_len)
        while p.ndim < k.ndim:
            p = p[..., None]
        ok = (k <= q) | (k < p)
    elif kind == "full":
        ok = k < 2 ** 29  # everything except padding/empty sentinel slots
    else:
        raise ValueError(f"unknown mask kind {kind!r}")
    if kv_len is not None:  # cache validity mask
        ok = ok & (k < kv_len)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Dense attention (small contexts, decode step)
# ---------------------------------------------------------------------------
def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_pos: jax.Array, kv_pos: jax.Array, kind: str,
                    window: Optional[int] = None,
                    prefix_len: Optional[jax.Array] = None,
                    kv_len: Optional[jax.Array] = None) -> jax.Array:
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    Dv = v.shape[-1]
    G = H // KH
    qg = q.reshape(B, Sq, KH, G, D)
    scale = 1.0 / math.sqrt(D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
    bias = _mask_bias(q_pos, kv_pos, kind, window, prefix_len, kv_len)
    scores = scores + bias[:, None, None] if bias.ndim == 3 else scores + bias
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, H, Dv)


# ---------------------------------------------------------------------------
# Blockwise attention (online softmax [27]; FlashAttention-2 style, pure JAX)
#
# Forward never materializes [Sq, Skv]; the custom VJP saves only
# (q, k, v, o, logsumexp) and *recomputes* score blocks in the backward
# pass — O(S·D) residual memory instead of O(S²) (the difference between
# 43 GiB/device and ~2 GiB/device at 4k x batch-256 training).
# This is also the pure-jnp oracle for the Pallas flash_attention kernel.
# ---------------------------------------------------------------------------
def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        q_pos: jax.Array, kv_pos: jax.Array, kind: str,
                        window: Optional[int] = None,
                        prefix_len: Optional[jax.Array] = None,
                        q_block: int = 512, kv_block: int = 1024) -> jax.Array:
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    KH = k.shape[2]
    Dv = v.shape[-1]
    G = H // KH
    scale = 1.0 / math.sqrt(D)

    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    nq = -(-Sq // q_block)
    nk = -(-Skv // kv_block)
    pad_q = nq * q_block - Sq
    pad_k = nk * kv_block - Skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad_k)),
                         constant_values=2 ** 30)  # always-masked sentinel

    if prefix_len is None:
        pfx = jnp.zeros((), jnp.float32)
    else:
        pfx = jnp.asarray(prefix_len, jnp.float32)
    qp32 = q_pos.astype(jnp.float32)
    kp32 = kv_pos.astype(jnp.float32)

    # block views: [n_blocks, B, block, ...]
    def qsplit(a, n, blk):
        return a.reshape(B, n, blk, *a.shape[2:]).swapaxes(0, 1)

    def _fwd_impl(qf, kf, vf, qp, kp, pfx):
        def _bias(qp_i, kp_j):
            return _mask_bias(qp_i, kp_j, kind, window, pfx)
        qb = qsplit(qf, nq, q_block)
        qpb = qsplit(qp, nq, q_block)
        kb = qsplit(kf, nk, kv_block)
        vb = qsplit(vf, nk, kv_block)
        kpb = qsplit(kp, nk, kv_block)

        def q_block_fn(args):
            q_i, qp_i = args
            qg = q_i.reshape(B, q_block, KH, G, D)

            def kv_step(carry, inputs):
                m, l, acc = carry
                k_j, v_j, kp_j = inputs
                s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                               k_j).astype(jnp.float32) * scale
                s = s + _bias(qp_i, kp_j)[:, None, None, :, :]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v_j.dtype), v_j)
                acc_new = acc * corr[..., None].astype(acc.dtype) + pv
                return (m_new, l_new, acc_new), None

            m0 = jnp.full((B, KH, G, q_block), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, KH, G, q_block), jnp.float32)
            a0 = jnp.zeros((B, KH, G, q_block, Dv), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                          (kb, vb, kpb))
            out = acc / jnp.maximum(l, 1e-30)[..., None]
            # rows with no valid keys (padding) get L=+inf -> p==0 in bwd
            lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), 1e30)
            return (out.transpose(0, 3, 1, 2, 4).reshape(B, q_block, H, Dv),
                    lse)

        outs, lses = jax.lax.map(q_block_fn, (qb, qpb))
        out = outs.swapaxes(0, 1).reshape(B, nq * q_block, H, Dv)
        return out, lses                       # lses: [nq, B, KH, G, q_block]

    @jax.custom_vjp
    def fa(qf, kf, vf, qp, kp, pfx):
        out, _ = _fwd_impl(qf, kf, vf, qp, kp, pfx)
        return out

    def fa_fwd(qf, kf, vf, qp, kp, pfx):
        out, lses = _fwd_impl(qf, kf, vf, qp, kp, pfx)
        return out, (qf, kf, vf, qp, kp, pfx, out, lses)

    def fa_bwd(res, do):
        qf, kf, vf, qp, kp, pfx, out, lses = res

        def _bias(qp_i, kp_j):
            return _mask_bias(qp_i, kp_j, kind, window, pfx)
        do = do.astype(jnp.float32)
        qb = qsplit(qf, nq, q_block)
        qpb = qsplit(qp, nq, q_block)
        dob = qsplit(do, nq, q_block)
        ob = qsplit(out.astype(jnp.float32), nq, q_block)
        kb = qsplit(kf, nk, kv_block)
        vb = qsplit(vf, nk, kv_block)
        kpb = qsplit(kp, nk, kv_block)
        # D_i = rowsum(do * o):  [nq, B, KH, G, q_block]
        delta = jnp.einsum("nbqhd,nbqhd->nbqh", dob, ob)
        delta = delta.reshape(nq, B, q_block, KH, G).transpose(0, 1, 3, 4, 2)
        dog = dob.reshape(nq, B, q_block, KH, G, Dv)
        qg = qb.reshape(nq, B, q_block, KH, G, D)

        def kv_step(dq_acc, inputs):
            k_j, v_j, kp_j = inputs

            def per_q(args):
                q_i, qp_i, do_i, L_i, D_i = args
                s = jnp.einsum("bqhgd,bkhd->bhgqk", q_i,
                               k_j).astype(jnp.float32) * scale
                s = s + _bias(qp_i, kp_j)[:, None, None, :, :]
                p = jnp.exp(s - L_i[..., None])
                dv_j = jnp.einsum("bhgqk,bqhgd->bkhd", p, do_i)
                dp = jnp.einsum("bqhgd,bkhd->bhgqk", do_i,
                                v_j.astype(jnp.float32))
                ds = p * (dp - D_i[..., None]) * scale
                dq_i = jnp.einsum("bhgqk,bkhd->bqhgd", ds,
                                  k_j.astype(jnp.float32))
                dk_j = jnp.einsum("bhgqk,bqhgd->bkhd", ds, q_i)
                return dq_i, dk_j, dv_j

            dqs, dks, dvs = jax.lax.map(per_q, (qg, qpb, dog, lses, delta))
            return dq_acc + dqs, (jnp.sum(dks, 0), jnp.sum(dvs, 0))

        dq0 = jnp.zeros((nq, B, q_block, KH, G, D), jnp.float32)
        dq, (dks, dvs) = jax.lax.scan(kv_step, dq0, (kb, vb, kpb))
        dq = dq.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * q_block, H, D)
        dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, nk * kv_block, KH, D)
        dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, nk * kv_block, KH, Dv)
        return (dq.astype(qf.dtype), dk.astype(kf.dtype),
                dv.astype(vf.dtype), jnp.zeros_like(qp), jnp.zeros_like(kp),
                jnp.zeros_like(pfx))

    fa.defvjp(fa_fwd, fa_bwd)
    out = fa(q, k, v, qp32, kp32, pfx)
    return out[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# Ring-buffer cache update
# ---------------------------------------------------------------------------
def _ring_update(buf: jax.Array, new: jax.Array, idx: jax.Array,
                 valid_len: Optional[jax.Array] = None) -> jax.Array:
    """Write ``new`` (S entries starting at logical position ``idx[b]`` per
    batch row) into a capacity-``cap`` ring buffer keyed by
    ``slot = position % cap``.  ``idx``: int32 [B] (per-slot indices for
    continuous batching).

    ``valid_len`` (int32 [B], default S): number of *leading* valid
    entries — bucket-padded prefill marks its pad suffix invalid so pads
    never consume ring capacity.  When the write overflows the ring
    (S >= cap) the survivors are the last ``cap`` VALID entries, not the
    last ``cap`` positions — otherwise a masked pad suffix would evict
    real in-window tokens from sliding-window caches.

    Alias-friendly fast paths (XLA can update donated buffers in place):
      * S == 1 (decode): one batched dynamic_update_slice at idx % cap.
      * S >= cap (window-cache prefill): a per-row dynamic slice of the
        last ``cap`` valid entries; a small per-row roll aligns them to
        their slots.
    The general wrapped case (chunked prefill continuation) falls back to
    a scatter.
    """
    cap = buf.shape[1]
    S = new.shape[1]
    start = (idx % cap).astype(jnp.int32)
    zeros = (0,) * (buf.ndim - 2)
    if S == 1:
        return jax.vmap(
            lambda b, n, s: jax.lax.dynamic_update_slice(b, n, (s, *zeros))
        )(buf, new, start)
    if S >= cap:
        if valid_len is None:
            s0 = jnp.full_like(idx, S - cap)
        else:
            # first surviving entry: last cap valid ones (clamped so a
            # short valid prefix keeps its masked-pad tail in range)
            s0 = jnp.clip(valid_len - cap, 0, S - cap).astype(jnp.int32)
        tail = jax.vmap(
            lambda t, s: jax.lax.dynamic_slice_in_dim(t, s, cap, 0)
        )(new, s0)
        # slot of the first tail element: (idx + s0) % cap
        shift = ((idx + s0) % cap).astype(jnp.int32)
        return jax.vmap(lambda t, s: jnp.roll(t, s, axis=0))(tail, shift)
    # general wrapped case (chunked prefill continuation): scatter;
    # invalid (pad) entries are routed to the out-of-range slot ``cap``
    # and dropped, preserving whatever the ring already holds there
    slots = (start[:, None] + jnp.arange(S)[None, :]) % cap     # [B, S]
    if valid_len is not None:
        slots = jnp.where(jnp.arange(S)[None, :] < valid_len[:, None],
                          slots, cap)
    return jax.vmap(
        lambda b, s, n: b.at[s].set(n, mode="drop"))(buf, slots, new)


# ---------------------------------------------------------------------------
# Paged (block-table) cache update
# ---------------------------------------------------------------------------
def _paged_update(pool: jax.Array, new: jax.Array, block_tables: jax.Array,
                  idx: jax.Array, layer: jax.Array,
                  valid_len: Optional[jax.Array] = None) -> jax.Array:
    """Write ``new`` (S entries starting at logical position ``idx[b]``
    per batch row) into layer ``layer`` of a stacked block pool through
    per-row block tables.

    pool [L, NB, bs, ...]; new [B, S, ...]; block_tables [B, nb] int32;
    idx [B]; layer an int32 scalar.  Position p lands in pool block
    ``[layer, block_tables[b, p//bs]]`` at offset ``p % bs``: one scatter
    into the stacked pool, which XLA applies in place when the pool is
    a donated buffer or a loop carry.  Invalid writes — pad entries
    beyond ``valid_len``, positions past the table (sentinel-index
    rows), or entries whose logical block is unallocated (table entry 0,
    the reserved null block) — are routed out of range and dropped, so
    the null block stays pristine and rows never write through a stale
    or foreign table entry.  Blocks are sequence-exclusive, so valid
    writes never collide across rows.
    """
    NB, bs = pool.shape[1], pool.shape[2]
    S = new.shape[1]
    nb = block_tables.shape[1]
    p = idx[:, None].astype(jnp.int32) + jnp.arange(S, dtype=jnp.int32)[None]
    logical = p // bs
    offs = p % bs
    safe_logical = jnp.clip(logical, 0, nb - 1)
    phys = jnp.take_along_axis(block_tables.astype(jnp.int32), safe_logical,
                               axis=1)
    invalid = (logical >= nb) | (logical < 0) | (phys <= 0)
    if valid_len is not None:
        invalid |= jnp.arange(S, dtype=jnp.int32)[None] >= valid_len[:, None]
    phys = jnp.where(invalid, NB, phys)       # out of range -> dropped
    return pool.at[layer, phys, offs].set(new.astype(pool.dtype),
                                          mode="drop")


def _gather_paged(pool: jax.Array, block_tables: jax.Array,
                  layer: jax.Array) -> jax.Array:
    """Gather a row-linear [B, nb*bs, ...] view of layer ``layer`` of a
    stacked block pool [L, NB, bs, ...] straight from the stack (the
    multi-token/chunked-prefill oracle path; unallocated table entries
    read the all-empty null block and self-mask)."""
    B, nb = block_tables.shape
    bs = pool.shape[2]
    g = pool[layer, block_tables.astype(jnp.int32)]
    return g.reshape(B, nb * bs, *pool.shape[3:])


# ---------------------------------------------------------------------------
# Full module apply
# ---------------------------------------------------------------------------
DENSE_SEQ_THRESHOLD = 2048


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(batch, position, head) symmetric int8 (paper's INT8 CIM mode
    applied to the decode state).  x: [B, S, KH, D]."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = (amax * (1.0 / 127.0) + 1e-12).astype(jnp.float32)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale[..., 0]


def _dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale[..., None]


def _decode_attention_cached(q, ck, cv, cpos, q_pos, k_scale, v_scale,
                             window):
    """One-token decode over the ring cache on the CIM flash-decode
    kernel (interpret oracle on CPU), TP-sharded over KV heads when an
    active model mesh divides them — each shard then holds 1/p of the
    KV cache and runs the kernel on its own heads, no collectives.

    q [B, 1, H, D]; ck/cv [B, S, KH, D] (int8 with [B, S, KH] scales on
    the quantized path); returns [B, 1, H, D].
    """
    from repro.kernels import ops as kops
    from repro.kernels.ref import decode_attention_ref
    from repro.quant import tp as _tp

    B, _, H, D = q.shape
    KH = ck.shape[2]
    q4 = q[:, 0].reshape(B, KH, H // KH, D)
    use_kernel = _resolve_use_kernel(None)
    mesh = _tp_mesh_for(KH)
    if mesh is not None:
        out4 = _tp.decode_attn(mesh, q4, ck, cv, cpos, q_pos, k_scale,
                               v_scale, window=window,
                               use_kernel=use_kernel)
    elif use_kernel:
        out4 = kops.decode_attention(q4, ck, cv, cpos, q_pos,
                                     k_scale=k_scale, v_scale=v_scale,
                                     window=window)
    else:
        out4 = decode_attention_ref(q4, ck, cv, cpos, q_pos, window=window,
                                    k_scale=k_scale, v_scale=v_scale)
    return out4.reshape(B, 1, H, D).astype(q.dtype)


def _decode_attention_paged_cached(q, ck, cv, cpos, bt, q_pos, layer,
                                   k_scale, v_scale, window):
    """One-token decode over layer ``layer`` of the paged (block-table)
    cache: same kernel/oracle/TP dispatch as
    :func:`_decode_attention_cached`, with the stacked KV pools streamed
    through the scalar-prefetched block table and layer index.

    q [B, 1, H, D]; pools [L, NB, bs, KH, D] (int8 with [L, NB, bs, KH]
    scales on the quantized path); bt [B, nb]; returns [B, 1, H, D].
    """
    from repro.kernels import ops as kops
    from repro.kernels.ref import decode_attention_paged_ref
    from repro.quant import tp as _tp

    B, _, H, D = q.shape
    KH = ck.shape[3]
    q4 = q[:, 0].reshape(B, KH, H // KH, D)
    use_kernel = _resolve_use_kernel(None)
    mesh = _tp_mesh_for(KH)
    if mesh is not None:
        out4 = _tp.decode_attn_paged(mesh, q4, ck, cv, cpos, bt, q_pos,
                                     layer, k_scale, v_scale, window=window,
                                     use_kernel=use_kernel)
    elif use_kernel:
        out4 = kops.decode_attention_paged(q4, ck, cv, cpos, bt, q_pos,
                                           layer, k_scale_pages=k_scale,
                                           v_scale_pages=v_scale,
                                           window=window)
    else:
        out4 = decode_attention_paged_ref(q4, ck, cv, cpos, bt, q_pos,
                                          layer, window=window,
                                          k_scale_pages=k_scale,
                                          v_scale_pages=v_scale)
    return out4.reshape(B, 1, H, D).astype(q.dtype)


def _paged_cache_apply(cache, k, v, positions, q, mask_kind, window,
                       prefix_len):
    """Cache write + attend for one layer of a paged (block-table) cache.

    ``cache`` holds the scan group's stacked block pools (``*_pages``,
    [L, NB, bs, ...]), which ride ``Model._stack``'s layer-scan carry,
    this layer's index ``layer``, and this layer's per-row
    ``block_tables``/``index``.  The new K/V, scales and positions
    scatter into the stacked pools at ``[layer, block, offset]``, and
    attention reads them at ``layer`` (the decode kernel through its
    scalar-prefetched layer index, chunked prefill through one gather),
    so no one-layer pool is sliced out or written back and XLA updates
    the donated pools in place.  Returns (out, new cache without
    ``layer``).
    """
    idx = cache["index"]
    bt = cache["block_tables"]
    layer = cache["layer"]
    S = positions.shape[1]
    valid_len = jnp.sum(positions < 2 ** 29, axis=1).astype(jnp.int32)
    quantized = cache["k_pages"].dtype == jnp.int8

    def write(name, new):
        return _paged_update(cache[name], new, bt, idx, layer, valid_len)

    cks = cvs = None
    if quantized:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        ck, cv = write("k_pages", kq), write("v_pages", vq)
        cks, cvs = write("k_scale_pages", ks), write("v_scale_pages", vs)
    else:
        ck, cv = write("k_pages", k), write("v_pages", v)
    cpos = write("pos_pages", positions)
    new_cache = {"k_pages": ck, "v_pages": cv, "pos_pages": cpos,
                 "block_tables": bt, "index": idx + S}
    if quantized:
        new_cache["k_scale_pages"] = cks
        new_cache["v_scale_pages"] = cvs
    if S == 1 and mask_kind in ("causal", "sliding", "prefix"):
        out = _decode_attention_paged_cached(
            q, ck, cv, cpos, bt, positions[:, 0], layer, cks, cvs,
            window if mask_kind == "sliding" else None)
    else:
        # chunked-prefill / multi-token oracle path: gather this layer
        # of the pools into the row-linear layout (XLA dequant on the
        # int8 path).  K/V are gathered straight from the stacked pools,
        # so no one-layer K/V pool is materialised.  The position and
        # scale pools end in dims narrower than a lane, which the TPU
        # stores transposed, and a gather relays out its whole operand:
        # they are gathered from their one layer, a relayout of one
        # layer's bytes instead of the stack's.
        def gather_small(pool):
            one = jax.lax.dynamic_slice_in_dim(pool, layer, 1, 0)
            return _gather_paged(one, bt, 0)

        k_lin, v_lin = _gather_paged(ck, bt, layer), _gather_paged(cv, bt,
                                                                   layer)
        pos_lin = gather_small(cpos)
        if quantized:
            k_lin = _dequantize_kv(k_lin, gather_small(cks)).astype(q.dtype)
            v_lin = _dequantize_kv(v_lin, gather_small(cvs)).astype(q.dtype)
        out = dense_attention(q, k_lin, v_lin, positions, pos_lin, mask_kind,
                              window, prefix_len)
    return out, new_cache


def attention_apply(
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    mask_kind: str = "causal",
    window: Optional[int] = None,
    prefix_len: Optional[jax.Array] = None,
    rope_theta: float = 10000.0,
    cache: Optional[dict] = None,
    use_rope: bool = True,
    residual: Optional[jax.Array] = None,
) -> tuple[jax.Array, Optional[dict]]:
    """Self-attention over ``x`` [B, S, d].

    cache: {"k","v": [B, S_max, KH, D], "index": int32 scalar} — decode
    appends at ``index`` and attends over the valid prefix.  Returns
    (output [B, S, d], updated cache or None).

    ``residual`` (the block input, pre-norm) is added to the output when
    given; on the quantized path the add happens inside the
    out-projection GEMM's epilogue (the paper's post-processing unit),
    so the projection output never exists as a separate tensor.

    QuantPlan-covered layers hold :class:`QuantizedLinear` leaves: a
    fused ``"qkv"`` weight ([d, H+2*KH, Dh] int8 — all three projections
    as ONE wide quantize-in-kernel GEMM dispatch, split along the head
    axis after) and/or an ``"o"`` weight ([H, Dh, d] int8).
    """
    B, S, _ = x.shape
    qkv_w = params.get("qkv")
    if isinstance(qkv_w, QuantizedLinear):
        o_w = params["o"]
        H = (o_w.q if isinstance(o_w, QuantizedLinear) else o_w).shape[0]
        KH = (qkv_w.q.shape[1] - H) // 2
        wide = quantized_qkv_proj(qkv_w, x).astype(x.dtype)
        q, k, v = jnp.split(wide, (H, H + KH), axis=2)
        q = shard(q, ("batch", "act_seq", "heads", None))
        k = shard(k, ("batch", "act_seq", "kv_heads", None))
        v = shard(v, ("batch", "act_seq", "kv_heads", None))
    else:
        q = shard(jnp.einsum("bsd,dhk->bshk", x, params["q"]),
                  ("batch", "act_seq", "heads", None))
        k = shard(jnp.einsum("bsd,dhk->bshk", x, params["k"]),
                  ("batch", "act_seq", "kv_heads", None))
        v = shard(jnp.einsum("bsd,dhk->bshk", x, params["v"]),
                  ("batch", "act_seq", "kv_heads", None))
    if "q_norm" in params:
        q = rmsnorm_apply(params["q_norm"], q)
        k = rmsnorm_apply(params["k_norm"], k)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)

    new_cache = None
    if cache is not None and "block_tables" in cache:
        # Paged (block-table) cache: fixed-size blocks from a shared
        # pool, routed per row by the block table (serving/paged_cache).
        out, new_cache = _paged_cache_apply(cache, k, v, positions, q,
                                            mask_kind, window, prefix_len)
    elif cache is not None:
        # Ring-buffer cache: slot = position % capacity.  Sliding-window
        # layers size capacity == window, so entries are overwritten exactly
        # when they leave the window; per-slot true positions drive masking.
        idx = cache["index"]
        # bucket-padded prefill marks pad positions with the empty
        # sentinel; those entries must not consume ring capacity
        valid_len = jnp.sum(positions < 2 ** 29, axis=1).astype(jnp.int32)
        quantized = cache["k"].dtype == jnp.int8
        cks = cvs = None
        if quantized:
            # int8 at write time: quantization is fused into the
            # cache-update site, so the cache never holds widened KV
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            ck = _ring_update(cache["k"], kq, idx, valid_len)
            cv = _ring_update(cache["v"], vq, idx, valid_len)
            cks = _ring_update(cache["k_scale"], ks, idx, valid_len)
            cvs = _ring_update(cache["v_scale"], vs, idx, valid_len)
        else:
            ck = _ring_update(cache["k"], k.astype(cache["k"].dtype), idx,
                              valid_len)
            cv = _ring_update(cache["v"], v.astype(cache["v"].dtype), idx,
                              valid_len)
        cpos = _ring_update(cache["pos"],
                            positions.astype(cache["pos"].dtype), idx,
                            valid_len)
        new_cache = {"k": ck, "v": cv, "pos": cpos, "index": idx + S}
        if quantized:
            new_cache["k_scale"] = cks
            new_cache["v_scale"] = cvs
        if S == 1 and mask_kind in ("causal", "sliding", "prefix"):
            # Single-token decode: the CIM flash-decode kernel streams
            # the (possibly int8) cache directly — in-kernel dequant,
            # never a widened KV tensor.  Every cached position is
            # <= q_pos, so the prefix mask reduces to causal here.
            out = _decode_attention_cached(
                q, ck, cv, cpos, positions[:, 0], cks, cvs,
                window if mask_kind == "sliding" else None)
        else:
            # chunked-prefill / multi-token oracle path (XLA dequant)
            if quantized:
                k_r = _dequantize_kv(ck, cks).astype(q.dtype)
                v_r = _dequantize_kv(cv, cvs).astype(q.dtype)
            else:
                k_r, v_r = ck, cv
            out = dense_attention(q, k_r, v_r, positions, cpos, mask_kind,
                                  window, prefix_len)
    else:
        kv_pos = positions
        if S <= DENSE_SEQ_THRESHOLD:
            out = dense_attention(q, k, v, positions, kv_pos, mask_kind,
                                  window, prefix_len)
        else:
            out = blockwise_attention(q, k, v, positions, kv_pos, mask_kind,
                                      window, prefix_len)

    o_w = params["o"]
    if isinstance(o_w, QuantizedLinear):
        # Out-projection on the fused pipeline; the residual rides in the
        # GEMM epilogue instead of a separate XLA add.
        o = quantized_out_proj(o_w, out, residual=residual).astype(x.dtype)
    else:
        o = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), o_w)
        if residual is not None:
            o = residual + o
    return o, new_cache


def init_kv_cache(batch: int, max_len: int, n_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16) -> dict:
    out = {
        "k": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        # true position held by each slot; +2**30 = empty ("future", so the
        # causal/sliding/prefix masks all exclude it)
        "pos": jnp.full((batch, max_len), 2 ** 30, jnp.int32),
        # per-slot write index (continuous batching: slots advance
        # independently)
        "index": jnp.zeros((batch,), jnp.int32),
    }
    if dtype == jnp.int8:
        out["k_scale"] = jnp.zeros((batch, max_len, n_kv_heads), jnp.float32)
        out["v_scale"] = jnp.zeros((batch, max_len, n_kv_heads), jnp.float32)
    return out


def init_paged_kv_cache(batch: int, num_blocks: int, block_size: int,
                        max_blocks: int, n_kv_heads: int, head_dim: int,
                        dtype=jnp.bfloat16) -> dict:
    """Paged KV state: shared fixed-size block pools + per-row block
    tables.  Physical block 0 is reserved as the null block — never
    allocated, all positions empty-sentinel — so zeroed table entries
    (unallocated logical blocks) read as fully masked."""
    out = {
        "k_pages": jnp.zeros((num_blocks, block_size, n_kv_heads, head_dim),
                             dtype),
        "v_pages": jnp.zeros((num_blocks, block_size, n_kv_heads, head_dim),
                             dtype),
        "pos_pages": jnp.full((num_blocks, block_size), 2 ** 30, jnp.int32),
        "block_tables": jnp.zeros((batch, max_blocks), jnp.int32),
        "index": jnp.zeros((batch,), jnp.int32),
    }
    if dtype == jnp.int8:
        out["k_scale_pages"] = jnp.zeros(
            (num_blocks, block_size, n_kv_heads), jnp.float32)
        out["v_scale_pages"] = jnp.zeros(
            (num_blocks, block_size, n_kv_heads), jnp.float32)
    return out


def paged_kv_cache_logical_axes(quantized: bool = False) -> dict:
    """Pools shard over KV heads (the head-parallel TP decode path holds
    1/p of every block); tables/indices are per-row host state."""
    out = {
        "k_pages": (None, None, "kv_heads", None),
        "v_pages": (None, None, "kv_heads", None),
        "pos_pages": (None, None),
        "block_tables": ("batch", None),
        "index": ("batch",),
    }
    if quantized:
        out["k_scale_pages"] = (None, None, "kv_heads")
        out["v_scale_pages"] = (None, None, "kv_heads")
    return out


def kv_cache_logical_axes(quantized: bool = False) -> dict:
    out = {
        "k": ("batch", "kv_seq", "kv_heads", None),
        "v": ("batch", "kv_seq", "kv_heads", None),
        "pos": ("batch", "kv_seq"),
        "index": ("batch",),
    }
    if quantized:
        out["k_scale"] = ("batch", "kv_seq", "kv_heads")
        out["v_scale"] = ("batch", "kv_seq", "kv_heads")
    return out
