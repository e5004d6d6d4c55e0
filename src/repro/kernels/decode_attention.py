"""Decode attention kernel — the GEMV-shaped workload the paper's CIM-MXU
accelerates (§IV-B: bit-serial broadcast of the single query against the
streamed KV cache, 72.7% faster than the systolic baseline).

TPU adaptation: flash-decode.  One query token per sequence attends over
the ring-buffer KV cache; the cache is streamed through VMEM in blocks
(the "weight update" side of the CIM analogy), with the online-softmax
state in scratch.  Per-slot true positions (ring-buffer semantics) drive
masking, so sliding-window layers work unchanged.

Three additions over the plain streaming kernel:

* **int8 KV** — when per-(slot, head) scales are given, K/V stream
  through VMEM as int8 (half the HBM traffic of the memory-bound decode
  GEMV) and dequantize *inside* the kernel: the scales factor out of
  both dots, so ``s = (q . k_q) * k_scale`` and ``o = (p * v_scale) . v_q``
  — no widened KV block is ever materialized.
* **block-skip list** — a scalar-prefetched per-(batch, kv-block) keep
  mask (SMEM, like the zero-capacity-expert skip in the grouped MoE
  kernel) guards the whole online-softmax step, so KV blocks that are
  fully masked (entirely beyond ``q_pos``, or entirely outside the
  sliding window) cost no MXU work.  Skipping is exact: a fully-masked
  block's probabilities underflow to exactly 0.0 in the streamed kernel
  too (see ``_block_keep`` for the all-masked-row exception).
* **split-KV** (flash-decode) — ``decode_attention_splitkv`` runs the
  KV walk as a 2D grid (splits x blocks-per-split), each split emitting
  its partial ``(o, m, l)`` softmax state, plus one small combine
  dispatch.  Long contexts parallelize over cores instead of
  serializing the kv-block loop.  At ``n_splits=1`` the combine's
  renormalization terms are exact identities (``exp(0) == 1``), so it
  matches the single-dispatch kernel bit-for-bit.

Grid: (B, kv_blocks) — kv innermost (splitkv: (B, NS, blocks)).  One
grid step takes every KV head of a KV block: Mosaic tiles the last two
dims of a block, so a block may not take one head of the KV-head axis
(second-to-last in [.., KH, D]).  A static loop over the heads runs the
online-softmax step per head; positions ride as a [.., 1, block_k] view
for the same reason.
q:   [B, KH, G, D]    (GQA groups factored)
k,v: [B, S, KH, D]    (bf16/f32, or int8 with [B, S, KH] f32 scales)
pos: [B, S] int32     (slot positions; 2**30 = empty)
q_pos: [B] int32      (current decode position)
out: [B, KH, G, D]
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
EMPTY_SLOT = 2 ** 30


def _keep_blocks(posb: jax.Array, q_pos: jax.Array, window) -> jax.Array:
    """Keep mask [B, nk] int32 from per-block positions [B, nk, block_k].

    A block is kept iff any of its slots is visible to the query.  One
    exception: a row with *no* visible slot anywhere (all-empty-sentinel
    cache) keeps every block — the streamed kernel then reproduces the
    reference's uniform-softmax output (all logits -1e30) instead of
    emitting zeros, so skip vs no-skip stays bit-identical in all cases.
    Shared by the ring (contiguous reshape) and paged (block-table
    gather) kernels so their skip decisions agree on equivalent layouts.
    """
    ok = posb <= q_pos[:, None, None]
    if window is not None:
        ok &= posb > (q_pos[:, None, None] - window)
    keep = ok.any(axis=-1)
    empty_row = ~keep.any(axis=1, keepdims=True)
    return (keep | empty_row).astype(jnp.int32)


def _block_keep(pos: jax.Array, q_pos: jax.Array, window,
                block_k: int) -> jax.Array:
    """Per-(batch, kv-block) keep mask [B, nk] for a contiguous cache."""
    B, S = pos.shape
    return _keep_blocks(pos.reshape(B, S // block_k, block_k), q_pos, window)


def _attend_block(q, k, v, kpos, qpos, m_prev, l_prev, acc_prev, *,
                  scale: float, window, k_scale=None, v_scale=None):
    """One online-softmax step of one head over a KV block.

    q [G, D]; k/v [block_k, D]; kpos [block_k]; running state m/l
    [G, 1], acc [G, D]; scales [block_k] or None (int8 K/V — dequantized
    here, scales factored out of the dots).  Returns the new (m, l, acc).
    """
    quantized = k_scale is not None
    if quantized:
        q = q.astype(jnp.float32)
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if quantized:
        s = s * k_scale[None, :]
    s = s * scale
    ok = kpos[None, :] <= qpos
    if window is not None:
        ok &= kpos[None, :] > qpos - window
    s = jnp.where(ok, s, NEG_INF)          # [G, block_k]

    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, -1, keepdims=True)
    if quantized:
        p = p * v_scale[None, :]
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return m_new, l_new, acc_prev * corr + pv


def _attend_heads(q_ref, k_ref, v_ref, pos_ref, ks_ref, vs_ref, qpos,
                  m_ref, l_ref, acc_ref, *, scale: float, window):
    """The online-softmax step for every KV head of one KV block.

    Blocks: q [1, KH, G, D]; k/v [1, block_k, KH, D]; pos [1, 1,
    block_k]; scales [1, block_k, KH] or None.  Scratch m/l [KH, G, 1],
    acc [KH, G, D].
    """
    kpos = pos_ref[0, 0]
    for h in range(q_ref.shape[1]):
        m, l, acc = _attend_block(
            q_ref[0, h], k_ref[0, :, h, :], v_ref[0, :, h, :], kpos, qpos,
            m_ref[h], l_ref[h], acc_ref[h], scale=scale, window=window,
            k_scale=None if ks_ref is None else ks_ref[0, :, h],
            v_scale=None if vs_ref is None else vs_ref[0, :, h])
        m_ref[h] = m
        l_ref[h] = l
        acc_ref[h] = acc


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _state_scratch(KH: int, G: int, D: int) -> list:
    return [pltpu.VMEM((KH, G, 1), jnp.float32),
            pltpu.VMEM((KH, G, 1), jnp.float32),
            pltpu.VMEM((KH, G, D), jnp.float32)]


def _split_refs(refs, quantized: bool, n_out: int):
    """(q, k, v, pos, k_scale|None, v_scale|None, outs, m, l, acc)."""
    q_ref, k_ref, v_ref, pos_ref = refs[:4]
    ks_ref = vs_ref = None
    rest = refs[4:]
    if quantized:
        ks_ref, vs_ref, rest = rest[0], rest[1], rest[2:]
    outs, (m_ref, l_ref, acc_ref) = rest[:n_out], rest[n_out:]
    return q_ref, k_ref, v_ref, pos_ref, ks_ref, vs_ref, outs, m_ref, \
        l_ref, acc_ref


def _decode_kernel(qpos_ref, skip_ref, *refs, scale: float, window,
                   n_kv_steps: int, quantized: bool):
    (q_ref, k_ref, v_ref, pos_ref, ks_ref, vs_ref, (o_ref,),
     m_ref, l_ref, acc_ref) = _split_refs(refs, quantized, 1)
    b, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    def _step():
        _attend_heads(q_ref, k_ref, v_ref, pos_ref, ks_ref, vs_ref,
                      qpos_ref[b], m_ref, l_ref, acc_ref, scale=scale,
                      window=window)

    pl.when(skip_ref[b, ki] > 0)(_step)

    @pl.when(ki == n_kv_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _kv_specs(block_k: int, KH: int, G: int, D: int, quantized: bool,
              nk_per_split: int | None = None):
    """in_specs shared by the single-dispatch and split partial kernels.

    Index maps take the grid indices plus the two prefetched scalar refs
    (q_pos, skip).  With ``nk_per_split`` the grid is (B, NS, ki) and
    the maps fold the (split, block) pair into the global kv-block
    index.  Every block spans all KV heads; int8 K/V blocks stream
    through VMEM with their [block_k, KH] f32 scale blocks alongside.
    """
    if nk_per_split is None:
        def blk(ki):
            return ki

        def im_q(b, ki, qp, sk):
            return (b, 0, 0, 0)
    else:
        def blk(si, ki):
            return si * nk_per_split + ki

        def im_q(b, si, ki, qp, sk):
            return (b, 0, 0, 0)

    def im_kv(b, *rest):
        return (b, blk(*rest[:-2]), 0, 0)

    def im_pos(b, *rest):
        return (b, 0, blk(*rest[:-2]))

    def im_scale(b, *rest):
        return (b, blk(*rest[:-2]), 0)

    specs = [
        pl.BlockSpec((1, KH, G, D), im_q),
        pl.BlockSpec((1, block_k, KH, D), im_kv),
        pl.BlockSpec((1, block_k, KH, D), im_kv),
        pl.BlockSpec((1, 1, block_k), im_pos),
    ]
    if quantized:
        specs += [pl.BlockSpec((1, block_k, KH), im_scale),
                  pl.BlockSpec((1, block_k, KH), im_scale)]
    return specs


@functools.partial(jax.jit, static_argnames=("window", "block_k",
                                             "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     pos: jax.Array, q_pos: jax.Array,
                     k_scale: jax.Array | None = None,
                     v_scale: jax.Array | None = None, window=None,
                     block_k: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: [B, KH, G, D]; k/v: [B, S, KH, D]; pos: [B, S]; q_pos: [B].

    ``k_scale``/``v_scale`` [B, S, KH] f32 turn on the int8-KV path
    (K/V must then be int8).  S must be a multiple of ``block_k`` —
    ``ops.decode_attention`` pads with the empty-slot sentinel.  On the
    TPU ``block_k`` must be a multiple of 128 or all of S (the position
    block's lane dim).
    """
    B, KH, G, D = q.shape
    S = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    block_k = min(block_k, S)
    assert S % block_k == 0
    nk = S // block_k
    quantized = k_scale is not None
    skip = _block_keep(pos, q_pos, window, block_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nk),
        in_specs=_kv_specs(block_k, KH, G, D, quantized),
        out_specs=pl.BlockSpec((1, KH, G, D),
                               lambda b, ki, qp, sk: (b, 0, 0, 0)),
        scratch_shapes=_state_scratch(KH, G, D),
    )
    operands = (q, k, v, pos.reshape(B, 1, S)) \
        + ((k_scale, v_scale) if quantized else ())
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, window=window,
                          n_kv_steps=nk, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        interpret=interpret,
    )(q_pos.astype(jnp.int32), skip, *operands)


# ---------------------------------------------------------------------------
# Split-KV (flash-decode): per-split partial softmax state + tiny combine
# ---------------------------------------------------------------------------
def _decode_splitkv_kernel(qpos_ref, skip_ref, *refs, scale: float, window,
                           n_kv_steps: int, quantized: bool):
    """Partial kernel: grid (B, NS, blocks-per-split); each split walks
    its KV slice with the same online-softmax step and emits its raw
    (o, m, l) state — no division, the combine renormalizes."""
    (q_ref, k_ref, v_ref, pos_ref, ks_ref, vs_ref, (o_ref, mo_ref, lo_ref),
     m_ref, l_ref, acc_ref) = _split_refs(refs, quantized, 3)
    b, si, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    def _step():
        _attend_heads(q_ref, k_ref, v_ref, pos_ref, ks_ref, vs_ref,
                      qpos_ref[b], m_ref, l_ref, acc_ref, scale=scale,
                      window=window)

    pl.when(skip_ref[b, si * n_kv_steps + ki] > 0)(_step)

    @pl.when(ki == n_kv_steps - 1)
    def _finish():
        o_ref[0, 0] = acc_ref[...]
        mo_ref[0, 0] = m_ref[...]
        lo_ref[0, 0] = l_ref[...]


def _combine_kernel(o_ref, m_ref, l_ref, out_ref):
    """Combine dispatch: grid (B,); renormalize the NS partial states
    against the global running max and emit the final output row."""
    o = o_ref[0]                           # [NS, KH, G, D] f32
    m = m_ref[0]                           # [NS, KH, G, 1] f32
    l = l_ref[0]
    m_g = jnp.max(m, axis=0)               # [KH, G, 1]
    w = jnp.exp(m - m_g[None])             # [NS, KH, G, 1]
    l_g = jnp.sum(l * w, axis=0)
    acc = jnp.sum(o * w, axis=0)           # [KH, G, D]
    out_ref[0] = (acc / jnp.maximum(l_g, 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_k",
                                             "n_splits", "interpret"))
def decode_attention_splitkv(q: jax.Array, k: jax.Array, v: jax.Array,
                             pos: jax.Array, q_pos: jax.Array,
                             k_scale: jax.Array | None = None,
                             v_scale: jax.Array | None = None, window=None,
                             block_k: int = 512, n_splits: int = 2,
                             interpret: bool = False) -> jax.Array:
    """Flash-decode over ``n_splits`` parallel KV slices + one combine.

    Same contract as :func:`decode_attention`; the kv-block count must
    divide evenly into ``n_splits``.
    """
    B, KH, G, D = q.shape
    S = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    block_k = min(block_k, S)
    assert S % block_k == 0
    nk = S // block_k
    assert nk % n_splits == 0, (nk, n_splits)
    nk_s = nk // n_splits
    quantized = k_scale is not None
    skip = _block_keep(pos, q_pos, window, block_k)

    def im_part(b, si, ki, qp, sk):
        return (b, si, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_splits, nk_s),
        in_specs=_kv_specs(block_k, KH, G, D, quantized, nk_per_split=nk_s),
        out_specs=[
            pl.BlockSpec((1, 1, KH, G, D), im_part),
            pl.BlockSpec((1, 1, KH, G, 1), im_part),
            pl.BlockSpec((1, 1, KH, G, 1), im_part),
        ],
        scratch_shapes=_state_scratch(KH, G, D),
    )
    operands = (q, k, v, pos.reshape(B, 1, S)) \
        + ((k_scale, v_scale) if quantized else ())
    o_part, m_part, l_part = pl.pallas_call(
        functools.partial(_decode_splitkv_kernel, scale=scale, window=window,
                          n_kv_steps=nk_s, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_splits, KH, G, D), jnp.float32),
            jax.ShapeDtypeStruct((B, n_splits, KH, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_splits, KH, G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q_pos.astype(jnp.int32), skip, *operands)

    def im_all(b):
        return (b, 0, 0, 0, 0)

    return pl.pallas_call(
        _combine_kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, n_splits, KH, G, D), im_all),
            pl.BlockSpec((1, n_splits, KH, G, 1), im_all),
            pl.BlockSpec((1, n_splits, KH, G, 1), im_all),
        ],
        out_specs=pl.BlockSpec((1, KH, G, D), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        interpret=interpret,
    )(o_part, m_part, l_part)


# ---------------------------------------------------------------------------
# Paged (block-table) flash-decode: same online-softmax walk, but each KV
# block is fetched through a scalar-prefetched per-sequence block table
# instead of a contiguous slice — the kernel side of the paged KV cache
# (serving/paged_cache.py).  Pools are sequence-free and stacked over the
# layers of a scan group: [L, NB, bs, KH, D], read at a prefetched layer
# index, so the layer scan never slices a one-layer pool out of the stack.
# ---------------------------------------------------------------------------
def _decode_paged_kernel(qpos_ref, skip_ref, bt_ref, layer_ref, *refs,
                         scale: float, window, n_kv_steps: int,
                         quantized: bool):
    """The block table and layer index are consumed by the index maps
    only (they route the DMA); the kernel body is exactly the ring
    kernel's — that shared body plus a shared skip mask is what makes
    paged == ring bit-identical on equivalent layouts."""
    del bt_ref, layer_ref
    _decode_kernel(qpos_ref, skip_ref, *refs, scale=scale, window=window,
                   n_kv_steps=n_kv_steps, quantized=quantized)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def decode_attention_paged(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, pos_pages: jax.Array,
                           block_tables: jax.Array, q_pos: jax.Array,
                           layer: jax.Array,
                           k_scale_pages: jax.Array | None = None,
                           v_scale_pages: jax.Array | None = None,
                           window=None, interpret: bool = False) -> jax.Array:
    """q: [B, KH, G, D]; k/v pools: [L, NB, bs, KH, D]; pos_pages: [L,
    NB, bs]; block_tables: [B, nb] int32 (physical block per logical
    block; 0 is the reserved null block, kept all-empty so unallocated
    table entries self-mask); q_pos: [B]; layer: int32 scalar, the pool
    layer to attend over.

    ``k_scale_pages``/``v_scale_pages`` [L, NB, bs, KH] f32 turn on the
    int8-KV path (pools must then be int8).  Grid (B, nb): block ki of
    row b streams pool block ``[layer, block_tables[b, ki]]`` (all KV
    heads) via the scalar-prefetched table and layer index (the layer
    dim is squeezed out of every pool block), runs the ring kernel's
    online-softmax step, and the skip list (computed from the gathered
    per-block positions) elides fully-masked blocks exactly as on the
    ring path.
    """
    B, KH, G, D = q.shape
    NB, bs = pos_pages.shape[1:]
    nb = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)
    quantized = k_scale_pages is not None
    bt = block_tables.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    # K/V stream from the stacked pools at the layer index.  The position
    # and scale pools end in small dims ([bs] and [bs, KH]) that XLA
    # stores in a compact transposed layout and Mosaic reads only in the
    # padded row-major one, so every call relays them out: taking their
    # layer first keeps that to one layer's bytes, not the stack's.
    def one_layer(a):
        return jax.lax.dynamic_index_in_dim(a, layer[0], 0, keepdims=False)

    pos_layer = one_layer(pos_pages)
    skip = _keep_blocks(pos_layer[bt], q_pos, window)

    def im_q(b, ki, qp, sk, bt, ly):
        return (b, 0, 0, 0)

    def im_kv(b, ki, qp, sk, bt, ly):
        return (ly[0], bt[b, ki], 0, 0, 0)

    def im_pos(b, ki, qp, sk, bt, ly):
        return (bt[b, ki], 0, 0)

    def im_scale(b, ki, qp, sk, bt, ly):
        return (bt[b, ki], 0, 0)

    in_specs = [
        pl.BlockSpec((1, KH, G, D), im_q),
        pl.BlockSpec((pl.Squeezed(), 1, bs, KH, D), im_kv),
        pl.BlockSpec((pl.Squeezed(), 1, bs, KH, D), im_kv),
        pl.BlockSpec((1, 1, bs), im_pos),
    ]
    if quantized:
        in_specs += [pl.BlockSpec((1, bs, KH), im_scale),
                     pl.BlockSpec((1, bs, KH), im_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KH, G, D), im_q),
        scratch_shapes=_state_scratch(KH, G, D),
    )
    operands = (q, k_pages, v_pages, pos_layer.reshape(NB, 1, bs)) \
        + ((one_layer(k_scale_pages), one_layer(v_scale_pages))
           if quantized else ())
    return pl.pallas_call(
        functools.partial(_decode_paged_kernel, scale=scale, window=window,
                          n_kv_steps=nb, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        interpret=interpret,
    )(q_pos.astype(jnp.int32), skip, bt, layer, *operands)
