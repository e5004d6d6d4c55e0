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

The paged kernel (``decode_attention_paged``) walks a block-table cache
instead: one grid step per row, an in-kernel loop over the row's live
compute blocks of several pool pages each (``paged_block_pages``), every
page DMA'd from the pool in HBM into a double-buffered VMEM block, then
the same online-softmax step over the whole block.

Ring grid: (B, kv_blocks) — kv innermost (splitkv: (B, NS, blocks)).  One
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
import numpy as np
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
# Paged (block-table) flash-decode: the same online-softmax step over a
# paged KV cache (serving/paged_cache.py).  Pools are sequence-free and
# stacked over the layers of a scan group: [L, NB, bs, KH, D], read at a
# prefetched layer index, so the layer scan never slices a one-layer pool
# out of the stack.  A grid step takes one row and walks only its live
# compute blocks of several pool pages each, DMAing every page from the
# pool in HBM into a double-buffered VMEM block.
# ---------------------------------------------------------------------------
PAGED_BLOCK_TOKENS = 512         # K/V tokens a compute block aims to hold


def paged_block_pages(bs: int, nb: int) -> int:
    """Pool pages per compute block of :func:`decode_attention_paged`:
    about ``PAGED_BLOCK_TOKENS`` tokens of ``bs``-token pages, and no more
    pages than the table's ``nb``."""
    return max(1, min(nb, PAGED_BLOCK_TOKENS // bs))


def paged_walk_pages(n_tokens, bs: int, nb: int):
    """Table entries the causal walk of :func:`decode_attention_paged`
    covers for a row attending positions ``0..n_tokens - 1`` (int or
    array): the pages of its compute blocks up to the last live one."""
    pages = paged_block_pages(bs, nb)
    return np.minimum(nb, -(-n_tokens // (pages * bs)) * pages)


def _decode_paged_kernel(qpos_ref, first_ref, last_ref, bt_ref, layer_ref,
                         q_ref, k_hbm, v_hbm, pos_ref, *refs, scale: float,
                         window, pages: int, quantized: bool):
    """One row.  Blocks: q [1, KH, G, D]; the row's positions [1, nc, T]
    and, int8, its scales [1, KH, nc, T]; ``k_hbm``/``v_hbm`` the whole
    pools [L, NB, bs, KH, D] in HBM.  Scratch: two [T, KH, D] buffers each
    for K and V (T = pages * bs), their DMA semaphores, m/l [KH, G, 1],
    acc [KH, G, D].

    Compute blocks ``first..last`` of the row are walked in order; the
    next block's page copies start before the current block is computed.
    The step per KV head is the ring kernel's, so at ``block_k == T`` on
    an equivalent layout the two agree bit for bit.  A block inside the
    range that the keep mask drops is computed all the same, which is
    exact: every probability of a fully masked block underflows to 0.0
    once a kept block has set the running max.
    """
    if quantized:
        ks_ref, vs_ref, *refs = refs
    o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    first, last, layer = first_ref[b], last_ref[b], layer_ref[0]
    bs = k_hbm.shape[2]

    def copies(c, slot):
        out = []
        for p in range(pages):
            page, rows = bt_ref[b, c * pages + p], pl.ds(p * bs, bs)
            out += [pltpu.make_async_copy(pool.at[layer, page],
                                          buf.at[slot, rows], sem.at[slot])
                    for pool, buf in ((k_hbm, kbuf), (v_hbm, vbuf))]
        return out

    _init_state(m_ref, l_ref, acc_ref)
    for cp in copies(first, 0):
        cp.start()
    qpos = qpos_ref[b]

    def body(c, carry):
        slot = (c - first) % 2

        @pl.when(c < last)
        def _next():
            for cp in copies(c + 1, 1 - slot):
                cp.start()

        for cp in copies(c, slot):
            cp.wait()
        kpos = pos_ref[0, c]
        for h in range(q_ref.shape[1]):
            m, l, acc = _attend_block(
                q_ref[0, h], kbuf[slot, :, h, :], vbuf[slot, :, h, :], kpos,
                qpos, m_ref[h], l_ref[h], acc_ref[h], scale=scale,
                window=window,
                k_scale=ks_ref[0, h, c] if quantized else None,
                v_scale=vs_ref[0, h, c] if quantized else None)
            m_ref[h] = m
            l_ref[h] = l
            acc_ref[h] = acc
        return carry

    jax.lax.fori_loop(first, last + 1, body, 0)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


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
    int8-KV path (pools must then be int8).

    Grid (B,).  The table is cut into compute blocks of ``P =``
    :func:`paged_block_pages` pages (T = P * bs tokens; entries past
    ``nb`` read the null block).  Each row's first and last kept compute
    block come from the ring kernel's keep mask over the gathered
    positions; row b walks only that range, copying the P pages of each
    block from pool layer ``layer`` into VMEM (double-buffered), and runs
    the ring kernel's online-softmax step over all T tokens for every KV
    head.  A row that sees nothing keeps every block, as on the ring
    path.  The row's positions and scales are gathered from the
    layer's pools before the call into lane-dense [B, nc, T] and [B, KH,
    nc, T] rows.
    """
    B, KH, G, D = q.shape
    bs = pos_pages.shape[2]
    nb = block_tables.shape[1]
    pages = paged_block_pages(bs, nb)
    nc = -(-nb // pages)
    T = pages * bs
    scale = 1.0 / math.sqrt(D)
    quantized = k_scale_pages is not None
    bt = jnp.pad(block_tables.astype(jnp.int32),
                 ((0, 0), (0, nc * pages - nb)))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    # The position and scale pools end in small dims ([bs] and [bs, KH])
    # that XLA stores in a compact transposed layout, so a gather from
    # them relays out its operand: taking the layer first keeps that to
    # one layer's bytes, not the stack's.
    def rows(pool):
        one = jax.lax.dynamic_index_in_dim(pool, layer[0], 0, keepdims=False)
        return one[bt].reshape(B, nc, T, *pool.shape[3:])

    pos = rows(pos_pages)
    keep = _keep_blocks(pos, q_pos, window) > 0
    blk = jnp.arange(nc, dtype=jnp.int32)
    first = jnp.min(jnp.where(keep, blk, nc), axis=1)
    last = jnp.max(jnp.where(keep, blk, -1), axis=1)

    def row_scales(pool):
        return rows(pool).transpose(0, 3, 1, 2)             # [B, KH, nc, T]

    def im_row(b, *_):
        return (b, 0, 0, 0)

    in_specs = [pl.BlockSpec((1, KH, G, D), im_row),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, nc, T), lambda b, *_: (b, 0, 0))]
    operands = [q, k_pages, v_pages, pos]
    if quantized:
        in_specs += [pl.BlockSpec((1, KH, nc, T), im_row)] * 2
        operands += [row_scales(k_scale_pages), row_scales(v_scale_pages)]
    buf = pltpu.VMEM((2, T, KH, D), k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KH, G, D), im_row),
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2,))]
        + _state_scratch(KH, G, D),
    )
    return pl.pallas_call(
        functools.partial(_decode_paged_kernel, scale=scale, window=window,
                          pages=pages, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        interpret=interpret,
    )(q_pos.astype(jnp.int32), first, last, bt, layer, *operands)


# ---------------------------------------------------------------------------
# Paged latent (MLA) decode: the absorbed form over int8 latent pools.
# Same block-table / layer-index grid as ``decode_attention_paged``, one
# grid step per (row, logical block); every head of the row is one MXU
# operand, so the score and output products are [H, r] x [r, bs] and
# [H, bs] x [bs, r] matmuls -- bound by compute at 128 heads.
# ---------------------------------------------------------------------------
def _mla_last_block(qpos, bs: int, nb: int):
    """Last logical block a row attends (0 for a row that attends
    nothing, whose grid steps then re-read one block and skip)."""
    return jnp.minimum(jnp.maximum(qpos, 0) // bs, nb - 1)


def _latent_step(ql, qr, lat, sc, sr, qpos, t0, scale, m_ref, l_ref,
                 acc_ref):
    """One online-softmax step of absorbed attention over a block of
    latents: ql [Q, r] / qr [Q, rope] queries, lat [bs, W] the block's
    latents and rope keys side by side (int8 or bf16), sc / sr [1, bs]
    their per-token scales, qpos the queries' positions (a scalar or [Q,
    1]), t0 the block's first position.  The MXU operands are bf16 (int8
    values convert exactly), accumulation f32; the scales factor out of
    both dots.  A query that sees no position of the block takes p = 0."""
    R, Dr = ql.shape[-1], qr.shape[-1]
    lat = lat.astype(jnp.bfloat16)
    c, kr = lat[:, :R], lat[:, R:R + Dr]
    dims = (((1,), (1,)), ((), ()))
    s = (jax.lax.dot_general(ql.astype(jnp.bfloat16), c, dims,
                             preferred_element_type=jnp.float32) * sc
         + jax.lax.dot_general(qr.astype(jnp.bfloat16), kr, dims,
                               preferred_element_type=jnp.float32) * sr)
    s = s * scale                                         # [Q, bs]
    t = t0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = t <= qpos
    s = jnp.where(seen, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
    pv = jax.lax.dot_general((p * sc).astype(jnp.bfloat16), c,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new


def _latent_init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _mla_decode_kernel(qpos_ref, bt_ref, layer_ref, ql_ref, qr_ref,
                       lat_ref, s_ref, o_ref, m_ref, l_ref, acc_ref, *,
                       scale: float, bs: int, n_kv_steps: int):
    """One (row, block) step.  Blocks: q_lat [1, H, r] and q_rope [1, H,
    rope]; the block's latents and rope keys side by side [bs, W >= r +
    rope] (int8 or bf16); their scales [1, 1, 2, bs].  Scratch m/l [H, 1],
    acc [H, r]."""
    del bt_ref, layer_ref
    b, ki = pl.program_id(0), pl.program_id(1)
    qpos = qpos_ref[b]

    @pl.when(ki == 0)
    def _init():
        _latent_init(m_ref, l_ref, acc_ref)

    @pl.when(ki * bs <= qpos)
    def _step():
        _latent_step(ql_ref[0], qr_ref[0], lat_ref[...],
                     s_ref[0, 0, 0:1, :], s_ref[0, 0, 1:2, :], qpos,
                     ki * bs, scale, m_ref, l_ref, acc_ref)

    @pl.when(ki == n_kv_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_decode_paged(q_lat: jax.Array, q_rope: jax.Array,
                     latent_pages: jax.Array, c_scale_pages: jax.Array,
                     r_scale_pages: jax.Array, block_tables: jax.Array,
                     q_pos: jax.Array, layer: jax.Array, scale: float,
                     interpret: bool = False) -> jax.Array:
    """q_lat: [B, H, r] (queries folded through W_UK); q_rope: [B, H,
    rope]; latent_pages: [L, NB, bs, W], each token's latent and rope key
    side by side in its first r + rope entries (int8 with per-token
    scales c_scale_pages /
    r_scale_pages [L, NB, bs], or bf16 with unit scales); block_tables
    [B, nb]; q_pos [B] (the row attends positions 0..q_pos; a row at or
    past the empty sentinel 2**29 attends nothing and returns zeros);
    layer: int32 scalar.  Returns [B, H, r] in q_lat's dtype:
    ``softmax(scale * (q_lat.c + q_rope.k_rope)) . c`` per head.

    Grid (B, nb): block ki of row b streams pool block ``[layer,
    block_tables[b, ki]]`` through the scalar-prefetched table and layer
    index.  Blocks past the row's last one map to that last block, so
    the pipeline issues no DMA for them, and their step is skipped.  The
    row's scales are gathered from the layer's scale pools before the
    call into [B, nb, 2, bs], so each step reads one lane-dense [2, bs]
    tile.
    """
    B, H, R = q_lat.shape
    Dr = q_rope.shape[-1]
    bs, W = latent_pages.shape[2:]
    nb = block_tables.shape[1]
    bt = block_tables.astype(jnp.int32)
    qpos = jnp.where(q_pos < 2 ** 29, q_pos, -1).astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def row_scales(pool):
        one = jax.lax.dynamic_index_in_dim(pool, layer[0], 0, keepdims=False)
        return one[bt]                                    # [B, nb, bs]

    scales = jnp.stack([row_scales(c_scale_pages), row_scales(r_scale_pages)],
                       axis=2)                            # [B, nb, 2, bs]

    def im_q(b, ki, qp, bt_, ly):
        return (b, 0, 0)

    def im_pool(b, ki, qp, bt_, ly):
        return (ly[0], bt_[b, jnp.minimum(ki, _mla_last_block(qp[b], bs, nb))],
                0, 0)

    def im_scale(b, ki, qp, bt_, ly):
        return (b, jnp.minimum(ki, _mla_last_block(qp[b], bs, nb)), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, H, R), im_q),
            pl.BlockSpec((1, H, Dr), im_q),
            pl.BlockSpec((pl.Squeezed(), pl.Squeezed(), bs, W), im_pool),
            pl.BlockSpec((1, 1, 2, bs), im_scale),
        ],
        out_specs=pl.BlockSpec((1, H, R), im_q),
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, R), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, scale=scale, bs=bs,
                          n_kv_steps=nb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), q_lat.dtype),
        interpret=interpret,
    )(qpos, bt, layer, q_lat, q_rope.astype(q_lat.dtype), latent_pages,
      scales)


# ---------------------------------------------------------------------------
# Paged latent (MLA) chunked prefill: the same absorbed step for every
# query of a chunk.  A grid step takes a tile of query rows (a few
# positions x every head) and walks the row's blocks in an in-kernel
# loop that stops at the tile's last position, DMAing each block from
# the pool (double-buffered), so a chunk costs its context and not the
# table's width, and no up-projected K/V or score matrix reaches HBM.
# ---------------------------------------------------------------------------
MLA_PREFILL_TILE_ROWS = 512      # query rows (positions x heads) a step


def _mla_prefill_kernel(last_ref, bt_ref, layer_ref, ql_ref, qr_ref,
                        qpos_ref, s_ref, lat_hbm, o_ref, buf, sem, m_ref,
                        l_ref, acc_ref, *, scale: float, bs: int):
    """One (row, query tile) step.  Blocks: q_lat [1, Q, r], q_rope [1, Q,
    rope], the rows' positions [1, Q, 1] (-1: a pad), the row's scales
    [1, 2, nb, bs]; ``lat_hbm`` the whole pool [L, NB, bs, W] in HBM.
    Scratch: two block buffers and their DMA semaphores, m/l [Q, 1], acc
    [Q, r]."""
    b, t = pl.program_id(0), pl.program_id(1)
    last = last_ref[b, t]                 # the tile's last position, or -1
    n = jnp.where(last >= 0, last // bs + 1, 0)
    layer = layer_ref[0]

    def fetch(i, slot):
        return pltpu.make_async_copy(lat_hbm.at[layer, bt_ref[b, i]],
                                     buf.at[slot], sem.at[slot])

    _latent_init(m_ref, l_ref, acc_ref)

    @pl.when(n > 0)
    def _first():
        fetch(0, 0).start()

    qpos = qpos_ref[0]

    def body(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n)
        def _next():
            fetch(i + 1, 1 - slot).start()

        fetch(i, slot).wait()
        _latent_step(ql_ref[0], qr_ref[0], buf[slot],
                     s_ref[0, 0, pl.ds(i, 1), :], s_ref[0, 1, pl.ds(i, 1), :],
                     qpos, i * bs, scale, m_ref, l_ref, acc_ref)
        return carry

    jax.lax.fori_loop(0, n, body, 0)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_prefill_paged(q_lat: jax.Array, q_rope: jax.Array,
                      latent_pages: jax.Array, c_scale_pages: jax.Array,
                      r_scale_pages: jax.Array, block_tables: jax.Array,
                      positions: jax.Array, layer: jax.Array, scale: float,
                      interpret: bool = False) -> jax.Array:
    """Causal absorbed attention of a prefill chunk over the paged latent
    pool, the chunk's own latents already written.

    q_lat [B, S, H, r] (queries folded through W_UK); q_rope [B, S, H,
    rope]; the pools as for :func:`mla_decode_paged`; block_tables [B,
    nb]; positions [B, S] (query s attends positions 0..positions[b, s];
    a position at or past the empty sentinel 2**29 attends nothing and
    returns zeros); layer: int32 scalar.  Returns [B, S, H, r] in q_lat's
    dtype, the same per-query math as :func:`mla_decode_paged`.

    Grid (B, query tiles): each tile holds ``MLA_PREFILL_TILE_ROWS // H``
    positions x all H heads as one MXU operand and loops over blocks
    0..(its last position) // bs, so blocks past the chunk cost neither a
    DMA nor a grid step.
    """
    B, S, H, R = q_lat.shape
    Dr = q_rope.shape[-1]
    bs = latent_pages.shape[2]
    nb = block_tables.shape[1]
    ts = max(1, MLA_PREFILL_TILE_ROWS // H)   # positions per tile
    pad = -S % ts
    qpos = jnp.where(positions < 2 ** 29, positions, -1).astype(jnp.int32)
    if pad:
        q_lat = jnp.pad(q_lat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_rope = jnp.pad(q_rope, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad)), constant_values=-1)
    Sp = S + pad
    nt, Q = Sp // ts, ts * H
    last = jnp.max(qpos.reshape(B, nt, ts), axis=-1)       # [B, nt]
    rows = jnp.repeat(qpos, H, axis=1)[..., None]          # [B, Sp*H, 1]
    bt = block_tables.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def row_scales(pool):
        one = jax.lax.dynamic_index_in_dim(pool, layer[0], 0, keepdims=False)
        return one[bt]                                     # [B, nb, bs]

    scales = jnp.stack([row_scales(c_scale_pages), row_scales(r_scale_pages)],
                       axis=1)                             # [B, 2, nb, bs]

    def im_rows(b, t, *_):
        return (b, t, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nt),
        in_specs=[
            pl.BlockSpec((1, Q, R), im_rows),
            pl.BlockSpec((1, Q, Dr), im_rows),
            pl.BlockSpec((1, Q, 1), im_rows),
            pl.BlockSpec((1, 2, nb, bs), lambda b, t, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Q, R), im_rows),
        scratch_shapes=[pltpu.VMEM((2,) + latent_pages.shape[2:],
                                   latent_pages.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((Q, 1), jnp.float32),
                        pltpu.VMEM((Q, 1), jnp.float32),
                        pltpu.VMEM((Q, R), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_mla_prefill_kernel, scale=scale, bs=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sp * H, R), q_lat.dtype),
        interpret=interpret,
    )(last, bt, layer, q_lat.reshape(B, Sp * H, R),
      q_rope.astype(q_lat.dtype).reshape(B, Sp * H, Dr), rows, scales,
      latent_pages)
    return out.reshape(B, Sp, H, R)[:, :S]
