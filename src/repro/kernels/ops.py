"""jit'd public wrappers around the Pallas kernels.

On CPU (this container) every op runs the kernel in ``interpret=True``
mode; on a real TPU backend the compiled kernels run natively.  The
wrappers handle padding to block multiples and the quantization epilogue
for the CIM INT8 path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref as _ref
from . import cim_gemm as _cg
from .cim_gemm import (cim_gemm_int8, cim_gemm_int8_fused,
                       cim_gemm_int8_fused_qin, cim_gated_gemm_int8,
                       cim_grouped_gemm_int8, cim_grouped_gated_gemm_int8,
                       CORE_K, CORE_N, MAX_FUSED_QUANT_K, MAX_FUSED_QUANT_N)
from . import decode_attention as _da
from .decode_attention import decode_attention as _decode_kernel
from .decode_attention import decode_attention_splitkv as _decode_splitkv
from .flash_attention import flash_attention as _flash_kernel
from .ssd_scan import ssd_scan as _ssd_kernel


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


# ---------------------------------------------------------------------------
# CIM quantized matmul (INT8 weight-stationary + dequant epilogue)
# ---------------------------------------------------------------------------
def quantize_weights_int8(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-output-channel symmetric int8: w [K, N] -> (w_q, scale [N])."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0) + 1e-12
    scale = amax * (1.0 / 127.0)  # not / 127.0: see quantize_rows_int8_ref
    w_q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127,
                   127).astype(jnp.int8)
    return w_q, scale.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cim_quantized_matmul(x: jax.Array, w_q: jax.Array, w_scale: jax.Array,
                         interpret: bool | None = None) -> jax.Array:
    """Dynamic-activation-quant INT8 matmul with dequant epilogue.

    x [M, K] bf16/f32; w_q [K, N] int8; w_scale [N] -> [M, N] float32.
    """
    interpret = _on_cpu() if interpret is None else interpret
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True) + 1e-12
    x_scale = amax * (1.0 / 127.0)
    x_q = jnp.clip(jnp.round(x32 / x_scale), -127, 127).astype(jnp.int8)

    x_q, M = _pad_to(x_q, 0, 256)
    x_q, K = _pad_to(x_q, 1, CORE_K)
    w_p, _ = _pad_to(w_q, 0, CORE_K)
    w_p, N = _pad_to(w_p, 1, CORE_N)
    acc = cim_gemm_int8(x_q, w_p, interpret=interpret)
    acc = acc[:M, :N].astype(jnp.float32)
    return acc * x_scale * w_scale[None, :]


# ---------------------------------------------------------------------------
# Fused INT8 epilogue pipeline (quant -> GEMM -> dequant/bias/act, one
# kernel per GEMM; the int32 accumulator never leaves VMEM)
# ---------------------------------------------------------------------------
def _pad_acts(x):
    """Pad activations to the kernel grid: M -> 256-mult, K -> CORE_K."""
    x_p, M = _pad_to(x, 0, 256)
    x_p, K = _pad_to(x_p, 1, CORE_K)
    return x_p, M, K


def _pad_weight(w_q, w_scale):
    """Pad an int8 weight + its [N] scale: K -> CORE_K, N -> CORE_N."""
    w_p, _ = _pad_to(w_q, 0, CORE_K)
    w_p, N = _pad_to(w_p, 1, CORE_N)
    ws_p, _ = _pad_to(w_scale[None, :], 1, CORE_N)
    return w_p, ws_p, N


def _pad_operands(x, w_q, w_scale, bias=None):
    """Pad (x int8-able acts, int8 weights, scales, bias) to block grids."""
    x_p, M, K = _pad_acts(x)
    w_p, ws_p, N = _pad_weight(w_q, w_scale)
    b_p = None
    if bias is not None:
        b_p, _ = _pad_to(bias.astype(jnp.float32)[None, :], 1, CORE_N)
    return x_p, w_p, ws_p, b_p, M, K, N


def _pad_residual(residual):
    """Pad a [M, N] residual to the (256, CORE_N) output grid."""
    if residual is None:
        return None
    r_p, _ = _pad_to(residual.astype(jnp.float32), 0, 256)
    r_p, _ = _pad_to(r_p, 1, CORE_N)
    return r_p


def quantize_rows_int8(x: jax.Array,
                       interpret: bool | None = None) -> tuple[jax.Array,
                                                               jax.Array]:
    """Pallas dynamic per-row activation quantization.

    x [M, K] f32/bf16 -> (q int8 [M, K], scale f32 [M, 1]); replaces the
    XLA abs/max/round/clip chain (the paper's pre-processing unit).
    """
    interpret = _on_cpu() if interpret is None else interpret
    x_p, M = _pad_to(x, 0, 256)
    x_p, K = _pad_to(x_p, 1, CORE_K)
    q, s = _cg.quantize_rows_int8(x_p, interpret=interpret)
    return q[:M, :K], s[:M]


@functools.partial(jax.jit, static_argnames=("activation", "out_dtype",
                                             "interpret"))
def cim_quantized_matmul_fused(x: jax.Array, w_q: jax.Array,
                               w_scale: jax.Array,
                               bias: jax.Array | None = None,
                               residual: jax.Array | None = None,
                               activation: str | None = None,
                               out_dtype=jnp.float32,
                               interpret: bool | None = None) -> jax.Array:
    """Fully fused quantized linear — one Pallas dispatch when K fits.

    x [M, K] bf16/f32; w_q [K, N] int8; w_scale [N]; optional bias [N],
    gelu/silu/relu epilogue, and residual [M, N] added after the
    activation -> [M, N] ``out_dtype``.  When the padded K extent fits
    the VMEM row budget (``MAX_FUSED_QUANT_K``) the activation quant
    happens *inside* the GEMM kernel (one dispatch, the attention
    QKV/out-proj path); wider K falls back to a separate quantize kernel
    (two dispatches).  Either way no XLA dequant/bias/activation ops run
    between kernels.
    """
    interpret = _on_cpu() if interpret is None else interpret
    x_p, w_p, ws_p, b_p, M, K, N = _pad_operands(x, w_q, w_scale, bias)
    r_p = _pad_residual(residual)
    if x_p.shape[1] <= MAX_FUSED_QUANT_K:
        out = cim_gemm_int8_fused_qin(x_p, w_p, ws_p, bias=b_p,
                                      residual=r_p, activation=activation,
                                      out_dtype=out_dtype,
                                      interpret=interpret)
    else:
        x_q, x_s = _cg.quantize_rows_int8(x_p, interpret=interpret)
        out = cim_gemm_int8_fused(x_q, w_p, x_s, ws_p, bias=b_p,
                                  residual=r_p, activation=activation,
                                  out_dtype=out_dtype, interpret=interpret)
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("interpret",))
def cim_int8_gemm_acc(x_q: jax.Array, w_q: jax.Array,
                      interpret: bool | None = None) -> jax.Array:
    """Padded int32-out INT8 GEMM: x_q [M, K] int8 @ w_q [K, N] int8 ->
    int32 [M, N].

    The tensor-parallel row-parallel shard path: each shard's partial
    accumulator is psum'd across the model axis (int32 addition is
    exact), and ONE dequant/residual epilogue runs on the summed
    accumulator — bit-identical to the unsharded fused pipeline.
    """
    interpret = _on_cpu() if interpret is None else interpret
    x_p, M = _pad_to(x_q, 0, 256)
    x_p, _ = _pad_to(x_p, 1, CORE_K)
    w_p, _ = _pad_to(w_q, 0, CORE_K)
    w_p, N = _pad_to(w_p, 1, CORE_N)
    return cim_gemm_int8(x_p, w_p, interpret=interpret)[:M, :N]


@functools.partial(jax.jit, static_argnames=("activation", "interpret"))
def cim_hidden_int8(x_q: jax.Array, x_scale: jax.Array, up_q: jax.Array,
                    up_scale: jax.Array, gate_q: jax.Array | None = None,
                    gate_scale: jax.Array | None = None,
                    activation: str = "gelu",
                    interpret: bool | None = None) -> jax.Array:
    """MLP front half from pre-quantized activations, f32 out, no
    requant: ``act(x@Wg) * (x@Wu)`` (or ``act(x@Wu)`` ungated).

    The tensor-parallel column shard of the MLP: each device computes
    its d_ff slice of the hidden state; the int8 requant runs *outside*
    with the row absmax pmax'd across shards (a local requant would use
    a different scale than the unsharded pipeline).
    """
    interpret = _on_cpu() if interpret is None else interpret
    x_p, M = _pad_to(x_q, 0, 256)
    x_p, _ = _pad_to(x_p, 1, CORE_K)
    s_p, _ = _pad_to(x_scale, 0, 256)
    up_p, us_p, N = _pad_weight(up_q, up_scale)
    if gate_q is not None:
        g_p, gs_p, _ = _pad_weight(gate_q, gate_scale)
        h = cim_gated_gemm_int8(x_p, g_p, up_p, s_p, gs_p, us_p,
                                activation=activation, quantize_out=False,
                                interpret=interpret)
    else:
        h = cim_gemm_int8_fused(x_p, up_p, s_p, us_p, activation=activation,
                                quantize_out=False, interpret=interpret)
    return h[:M, :N]


@functools.partial(jax.jit, static_argnames=("activation", "out_dtype",
                                             "interpret"))
def cim_quantized_mlp(x: jax.Array, up_q: jax.Array, up_scale: jax.Array,
                      down_q: jax.Array, down_scale: jax.Array,
                      gate_q: jax.Array | None = None,
                      gate_scale: jax.Array | None = None,
                      residual: jax.Array | None = None,
                      activation: str = "gelu", out_dtype=jnp.float32,
                      interpret: bool | None = None) -> jax.Array:
    """Fused INT8 MLP: quantize + (gated) up GEMM + down GEMM — 3 Pallas
    dispatches total, no XLA elementwise math between them.

    The up/gated kernel's epilogue computes ``act(gate) * up`` *and*
    re-quantizes the hidden state to int8 (when d_ff fits the VMEM row
    budget), so the down GEMM consumes int8 directly; neither the int32
    accumulators nor the f32 hidden state round-trip through HBM.
    ``residual [M, N]`` (the transformer-block skip connection) is added
    in the down GEMM's epilogue, so the MLP output never exists as a
    separate HBM tensor either.

    Weight padding short-circuits to a no-op when d_model/d_ff are
    already CORE_K/CORE_N-aligned (every real serving config); only
    toy/ragged dims pay a per-call pad copy.
    """
    interpret = _on_cpu() if interpret is None else interpret
    d_ff = up_q.shape[1]
    N = down_q.shape[1]

    x_p, M, _ = _pad_acts(x)
    up_p, us_p, _ = _pad_weight(up_q, up_scale)
    ff_p = up_p.shape[1]
    fuse_requant = ff_p <= MAX_FUSED_QUANT_N

    x_q, x_s = _cg.quantize_rows_int8(x_p, interpret=interpret)

    if gate_q is not None:
        g_p, gs_p, _ = _pad_weight(gate_q, gate_scale)
        h = cim_gated_gemm_int8(x_q, g_p, up_p, x_s, gs_p, us_p,
                                activation=activation,
                                quantize_out=fuse_requant,
                                interpret=interpret)
    else:
        h = cim_gemm_int8_fused(x_q, up_p, x_s, us_p, activation=activation,
                                quantize_out=fuse_requant,
                                interpret=interpret)
    if fuse_requant:
        h_q, h_s = h
    else:
        # d_ff too wide for the in-epilogue row reduction: one extra
        # quantize dispatch (still no XLA dequant/activation ops).
        h_q, h_s = _cg.quantize_rows_int8(h, interpret=interpret)

    # down's K dim must match the (256-padded) hidden width ff_p
    down_p, ds_p, _ = _pad_weight(
        jnp.pad(down_q, ((0, ff_p - d_ff), (0, 0))), down_scale)
    out = cim_gemm_int8_fused(h_q, down_p, h_s, ds_p,
                              residual=_pad_residual(residual),
                              out_dtype=out_dtype, interpret=interpret)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# Grouped-expert fused INT8 MLP pipeline (all experts per dispatch)
# ---------------------------------------------------------------------------
# Row alignment for the stacked per-expert capacity buffers: the int8
# sublane tile (32) rather than the dense path's 256, because the pad is
# paid E times over (E can be 60-256) and MoE capacities are small.
GROUP_ROW_ALIGN = 32


def _pad_grouped_acts(x):
    """Pad stacked acts [E, T, d]: T -> 32-mult, d -> CORE_K-mult."""
    x_p, _ = _pad_to(x, 1, GROUP_ROW_ALIGN)
    x_p, _ = _pad_to(x_p, 2, CORE_K)
    return x_p


def _pad_grouped_weight(w_q, w_scale):
    """Pad stacked int8 weights [E, K, N] + scales [E, N]: K -> CORE_K,
    N -> CORE_N multiples; returns (w_p, scale [E, 1, N_p], N)."""
    w_p, _ = _pad_to(w_q, 1, CORE_K)
    w_p, N = _pad_to(w_p, 2, CORE_N)
    ws_p, _ = _pad_to(w_scale[:, None, :], 2, CORE_N)
    return w_p, ws_p, N


@functools.partial(jax.jit, static_argnames=("activation", "out_dtype",
                                             "interpret"))
def cim_quantized_grouped_mlp(x: jax.Array, up_q: jax.Array,
                              up_scale: jax.Array, down_q: jax.Array,
                              down_scale: jax.Array,
                              gate_q: jax.Array | None = None,
                              gate_scale: jax.Array | None = None,
                              expert_counts: jax.Array | None = None,
                              groups: jax.Array | None = None,
                              activation: str = "gelu",
                              out_dtype=jnp.float32,
                              interpret: bool | None = None) -> jax.Array:
    """Fused INT8 MLPs for ALL E experts in a constant number of Pallas
    dispatches: one quantize over the stacked capacity rows + one grouped
    (gated) up GEMM + one grouped down GEMM — independent of E, where the
    per-expert loop traced 3·E dispatches.

    x [E, T, d] f32/bf16 (per-expert capacity buffers); up/gate weights
    [E, d, F] int8 with scales [E, F]; down [E, F, d'] int8, scale
    [E, d'] -> [E, T, d'] ``out_dtype``.  Identical per-row integer math
    to running :func:`cim_quantized_mlp` per expert (bit-for-bit — the
    parity is pinned in tests/test_quant.py): row quantization, int32
    accumulation, and the dequant/act/requant epilogues are all
    elementwise or exact, so grouping changes only the dispatch
    structure, never the numbers.

    ``expert_counts`` (int32 [E]) is the zero-capacity skip list,
    scalar-prefetched into both grouped kernels: experts that received
    no tokens skip their MXU dot products instead of streaming all-zero
    capacity rows through the grid — same dispatch count, same bits.

    Ragged form: with ``groups`` (int32 [n_tiles]) x is [n_tiles, tm, d]
    row tiles, tile t runs expert ``groups[t]``'s weights and
    ``expert_counts`` is per tile (0: an empty tile, which also fetches
    no weights).
    """
    interpret = _on_cpu() if interpret is None else interpret
    E, T, d = x.shape
    d_ff = up_q.shape[2]
    N = down_q.shape[2]

    x_p = _pad_grouped_acts(x)
    Tp, dp = x_p.shape[1:]
    up_p, us_p, _ = _pad_grouped_weight(up_q, up_scale)
    ff_p = up_p.shape[2]
    fuse_requant = ff_p <= MAX_FUSED_QUANT_N

    # ONE quantize dispatch over every expert's capacity rows
    x_q, x_s = _cg.quantize_rows_int8(x_p.reshape(E * Tp, dp),
                                      interpret=interpret)
    x_q = x_q.reshape(E, Tp, dp)
    x_s = x_s.reshape(E, Tp, 1)

    if gate_q is not None:
        g_p, gs_p, _ = _pad_grouped_weight(gate_q, gate_scale)
        h = cim_grouped_gated_gemm_int8(x_q, g_p, up_p, x_s, gs_p, us_p,
                                        counts=expert_counts, groups=groups,
                                        activation=activation,
                                        quantize_out=fuse_requant,
                                        interpret=interpret)
    else:
        h = cim_grouped_gemm_int8(x_q, up_p, x_s, us_p,
                                  counts=expert_counts, groups=groups,
                                  activation=activation,
                                  quantize_out=fuse_requant,
                                  interpret=interpret)
    if fuse_requant:
        h_q, h_s = h
    else:
        # d_expert too wide for the in-epilogue row reduction: one extra
        # quantize dispatch (still constant in E).
        h_q, h_s = _cg.quantize_rows_int8(h.reshape(E * Tp, ff_p),
                                          interpret=interpret)
        h_q = h_q.reshape(E, Tp, ff_p)
        h_s = h_s.reshape(E, Tp, 1)

    # down's K dim must match the (CORE_N-padded) hidden width ff_p
    down_p, ds_p, _ = _pad_grouped_weight(
        jnp.pad(down_q, ((0, 0), (0, ff_p - d_ff), (0, 0))), down_scale)
    out = cim_grouped_gemm_int8(h_q, down_p, h_s, ds_p,
                                counts=expert_counts, groups=groups,
                                out_dtype=out_dtype, interpret=interpret)
    return out[:, :T, :N]


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, causal=True, window=None, block_q=256,
                    block_k=512, interpret: bool | None = None):
    interpret = _on_cpu() if interpret is None else interpret
    return _flash_kernel(q, k, v, causal=causal, window=window,
                         block_q=block_q, block_k=block_k,
                         interpret=interpret)


def decode_attention(q, k, v, pos, q_pos, k_scale=None, v_scale=None,
                     window=None, block_k=512, n_splits: int | None = None,
                     interpret: bool | None = None):
    """Flash-decode over a (possibly int8) ring-buffer KV cache.

    ``k_scale``/``v_scale`` [B, S, KH] f32 turn on the int8-KV path
    (in-kernel dequant).  ``n_splits`` picks the split-KV mode: None
    auto-selects (1 below 2048 slots, up to 8 beyond — the combine
    dispatch only pays for itself once the serial kv-block walk
    dominates); 1 forces the classic single dispatch.  Pads S up to the
    kv-block size with empty-slot sentinel positions (self-masking).
    """
    interpret = _on_cpu() if interpret is None else interpret
    S = k.shape[1]
    bk = min(block_k, S)
    pad = -S % bk
    if pad:
        k, _ = _pad_to(k, 1, bk)
        v, _ = _pad_to(v, 1, bk)
        pos = jnp.pad(pos, ((0, 0), (0, pad)),
                      constant_values=_da.EMPTY_SLOT)
        if k_scale is not None:
            k_scale, _ = _pad_to(k_scale, 1, bk)
            v_scale, _ = _pad_to(v_scale, 1, bk)
    if k_scale is None and k.dtype != q.dtype:
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    nk = (S + pad) // bk
    if n_splits is None:
        n_splits = 1 if S <= 2048 else max(1, min(8, S // 2048))
    n_splits = min(n_splits, nk)
    while nk % n_splits:
        n_splits -= 1
    if n_splits > 1:
        return _decode_splitkv(q, k, v, pos, q_pos, k_scale, v_scale,
                               window=window, block_k=bk,
                               n_splits=n_splits, interpret=interpret)
    return _decode_kernel(q, k, v, pos, q_pos, k_scale, v_scale,
                          window=window, block_k=bk, interpret=interpret)


def decode_attention_paged(q, k_pages, v_pages, pos_pages, block_tables,
                           q_pos, layer, k_scale_pages=None,
                           v_scale_pages=None, window=None,
                           interpret: bool | None = None):
    """Flash-decode over one layer of a paged (block-table) KV cache.

    Pools [L, NB, bs, KH, D] hold the fixed-size KV blocks of every layer
    of a scan group, shared by all sequences; ``layer`` (int32 scalar)
    picks the layer the kernel reads, as a scalar-prefetch operand, so
    the layer scan hands over the stacked pools it carries and no
    one-layer pool is ever sliced out (``Model._stack``).
    ``block_tables`` [B, nb] int32 maps each row's logical blocks to
    physical pool blocks (0 = the reserved all-empty null block).
    ``k_scale_pages``/``v_scale_pages`` [L, NB, bs, KH] f32 turn on the
    int8-KV path (in-kernel dequant).  Each row walks only its live
    compute blocks of ``paged_block_pages(bs, nb)`` pages.  Bit-identical
    to :func:`decode_attention` at ``block_k`` = that block in tokens, on
    equivalent layouts (same online-softmax step, same keep mask —
    pinned in tests/test_serving.py).
    """
    interpret = _on_cpu() if interpret is None else interpret
    if k_scale_pages is None and k_pages.dtype != q.dtype:
        k_pages = k_pages.astype(q.dtype)
        v_pages = v_pages.astype(q.dtype)
    return _da.decode_attention_paged(
        q, k_pages, v_pages, pos_pages, block_tables, q_pos, layer,
        k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
        window=window, interpret=interpret)


def mla_decode_paged(q_lat, q_rope, latent_pages, c_scale_pages,
                     r_scale_pages, block_tables, q_pos, layer, scale,
                     interpret: bool | None = None):
    """Absorbed MLA decode over one layer of the paged latent pool
    (``kernels.decode_attention.mla_decode_paged``): q_lat [B, H, r],
    q_rope [B, H, rope]; pool [L, NB, bs, W >= r + rope] with per-token
    scales [L, NB, bs] for the latent and the rope key; returns [B, H,
    r]."""
    interpret = _on_cpu() if interpret is None else interpret
    return _da.mla_decode_paged(q_lat, q_rope, latent_pages, c_scale_pages,
                                r_scale_pages, block_tables, q_pos, layer,
                                scale=float(scale), interpret=interpret)


def mla_prefill_paged(q_lat, q_rope, latent_pages, c_scale_pages,
                      r_scale_pages, block_tables, positions, layer, scale,
                      interpret: bool | None = None):
    """Causal absorbed attention of a prefill chunk over one layer of the
    paged latent pool (``kernels.decode_attention.mla_prefill_paged``):
    q_lat [B, S, H, r], q_rope [B, S, H, rope], positions [B, S]; returns
    [B, S, H, r]."""
    interpret = _on_cpu() if interpret is None else interpret
    return _da.mla_prefill_paged(q_lat, q_rope, latent_pages, c_scale_pages,
                                 r_scale_pages, block_tables, positions,
                                 layer, scale=float(scale),
                                 interpret=interpret)


def decode_attention_splitkv(q, k, v, pos, q_pos, k_scale=None, v_scale=None,
                             window=None, block_k=512, n_splits=2,
                             interpret: bool | None = None):
    """Explicit split-KV entry (partial + combine dispatches even at
    ``n_splits=1``, where it matches :func:`decode_attention`
    bit-for-bit — the combine's renormalization is exact identities)."""
    interpret = _on_cpu() if interpret is None else interpret
    return _decode_splitkv(q, k, v, pos, q_pos, k_scale, v_scale,
                           window=window, block_k=min(block_k, k.shape[1]),
                           n_splits=n_splits, interpret=interpret)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
def ssd_scan(x, log_a, b, c, chunk=128, interpret: bool | None = None):
    interpret = _on_cpu() if interpret is None else interpret
    return _ssd_kernel(x, log_a, b, c, chunk=chunk, interpret=interpret)


# re-export oracles for convenience
ref = _ref
