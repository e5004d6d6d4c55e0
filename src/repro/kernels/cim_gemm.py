"""CIM-MXU GEMM kernels — TPU-native adaptation of the paper's INT8 mode.

The paper's CIM-MXU holds a (16x8 cores) x (128x256) weight tile resident
in SRAM and streams activations through it (weight-stationary, bit-serial
input broadcast, simultaneous compute + weight write).  The TPU analogue:

* INT8 x INT8 -> INT32 matmul blocks sized to the CIM tile structure —
  ``block_k`` multiples of 128 (core K dim), ``block_n`` multiples of 256
  (core N dim) — kept resident in VMEM across the M sweep (the Pallas
  grid orders K innermost so each weight block is loaded once per
  (m, n) tile, mirroring weight-stationarity);
* double-buffered weight DMA (Pallas pipelines block fetches with
  compute) standing in for the CIM macro's concurrent weight-port write.

Fused epilogue pipeline (pre/post-processing-unit mapping)
----------------------------------------------------------
The paper's MXU pipeline never round-trips intermediate tensors to HBM:
a *pre-processing unit* quantizes incoming activations and a
*post-processing unit* rescales (and, fused with the VPU, applies bias
and the nonlinearity) before results leave the unit.  The kernels here
mirror that structure one-for-one:

``quantize_rows_int8``  (pre-processing unit)
    Row-wise dynamic absmax int8 quantization as a single Pallas kernel:
    ``x [M, K] f32/bf16 -> (x_q int8, x_scale f32 [M, 1])``.  Replaces
    the XLA abs/max/round/clip chain that previously materialized an f32
    copy of the activations.

``cim_gemm_int8_fused``  (MXU + post-processing unit)
    INT8 GEMM whose int32 accumulator lives only in VMEM scratch; at the
    last K-step the epilogue applies ``acc * x_scale * w_scale`` (+ bias)
    (+ gelu/silu/relu) (+ ``residual`` — the transformer-block skip
    connection) and emits f32/bf16 — or, with ``quantize_out``,
    re-quantizes the row block to int8 so the *next* GEMM can consume it
    directly.  The int32 accumulator is never an HBM-resident output.

``cim_gemm_int8_fused_qin``  (pre- + post-processing unit in one)
    The same pipeline as a single dispatch: the row-absmax quantization
    runs in the kernel prologue (full-K blocks, guarded by
    ``MAX_FUSED_QUANT_K``), so attention QKV/out-projections are ONE
    kernel each — no int8 activation tensor ever exists in HBM.

``cim_gated_gemm_int8``  (fused gated MLP front half)
    Two weight-stationary GEMMs (gate and up projections) sharing one
    activation stream, with ``act(gate) * up`` computed in the epilogue.
    With ``quantize_out`` the result is emitted pre-quantized for the
    down projection, so a full gated MLP is exactly three Pallas
    dispatches: quantize -> gated GEMM -> down GEMM (previously 3 GEMM
    dispatches plus 5+ XLA quant/dequant/bias/activation ops with f32
    intermediates in HBM).

``cim_grouped_gemm_int8`` / ``cim_grouped_gated_gemm_int8``  (grouped experts)
    The fused pipelines batched over a leading **expert** grid dimension:
    stacked activations ``[E, M, K]`` against stacked weights/scales
    ``[E, K, N]`` / ``[E, 1, N]``, one output tile per (expert, m, n)
    grid cell — the CIM mapping where every expert's weight tile sits in
    its own macro sub-grid and the dispatched tokens stream through.  A
    whole MoE layer's expert compute is a **constant** number of Pallas
    dispatches (quantize + gated-grouped + down-grouped) independent of
    E, instead of the 3·E dispatches a per-expert Python loop traces.

``cim_gemm_int8`` keeps the unfused int32-out path for parity tests and
the fused-vs-unfused benchmark rows.

``quantize_out`` requires the full N extent in one block (the row absmax
is a cross-N reduction), i.e. ``grid_n == 1``; callers fall back to a
separate ``quantize_rows_int8`` dispatch when N exceeds the VMEM budget.

ops.py wraps these with padding + dispatch; ref.py holds the pure-jnp
oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# CIM core geometry (paper Table I): 128 x 256 per core.
CORE_K = 128
CORE_N = 256

# Above this many output columns the fused requant epilogue would hold
# the whole row block in VMEM; fall back to a separate quantize kernel.
MAX_FUSED_QUANT_N = 8192

# Above this many input columns the quantize-in-kernel GEMM variant
# (``cim_gemm_int8_fused_qin``) holds a full-K activation row block plus
# its f32 copy in VMEM and has to shrink its row block to fit; wider K
# falls back to a separate quantize dispatch.
MAX_FUSED_QUANT_K = 4096


def _fit(dim: int, block: int) -> int:
    block = min(block, dim)
    while dim % block:
        block //= 2
    return max(1, block)


# Mosaic's scoped-VMEM limit per kernel (16 MiB on v5e).  Pallas
# double-buffers every BlockSpec'd input and output, so the block
# pickers count each block twice, plus scratch and the kernel body's
# f32 temporaries, against VMEM_BUDGET_BYTES — 3/4 of the limit, the
# rest left to the compiler's own scratch.  Blocks are counted at their
# VMEM tile size: a [rows, 1] f32 column occupies 128 lanes, a [1, N]
# row 8 sublanes.
SCOPED_VMEM_BYTES = 16 * 1024 * 1024
VMEM_BUDGET_BYTES = SCOPED_VMEM_BYTES * 3 // 4
_COL_BYTES = 2 * 128 * 4      # one double-buffered [rows, 1] f32 row
_ROW_BYTES = 2 * 8 * 4        # one double-buffered [1, N] f32 column


def _fit_rows(m_dim: int, block_m: int, row_bytes: int,
              fixed_bytes: int = 0) -> int:
    """Shrink ``block_m`` (floor 8 rows) until ``fixed_bytes + block_m
    * row_bytes`` fits the VMEM budget, then fit it to divide ``m_dim``.
    ``row_bytes`` is everything one row of the block costs (double
    buffers and temporaries included).  Row-wise kernels are
    bit-identical under any row blocking, so this only trades
    dispatch-grid granularity for footprint."""
    while block_m > 8 and fixed_bytes + block_m * row_bytes \
            > VMEM_BUDGET_BYTES:
        block_m //= 2
    return _fit(m_dim, block_m)


def _fit_qout_blocks(M: int, K: int, N: int, block_m: int, block_k: int,
                     n_mats: int, x_bytes: int = 1,
                     has_bias: bool = False) -> tuple[int, int]:
    """Block sizes for a ``quantize_out`` GEMM: the cross-N row
    reduction pins a full-N block, so VMEM is bought back by shrinking
    ``block_k`` (weight-stream granularity, floor CORE_K) and then
    ``block_m`` (rows in flight, floor 8).  ``n_mats`` is the number of
    weight matrices streamed (2 for the gated kernel), which also sets
    the int32 scratch accumulator count."""
    def fixed(bk: int) -> int:
        # double-buffered weight and scale (+ bias) blocks
        return n_mats * (2 * bk * N + _ROW_BYTES * N) \
            + (_ROW_BYTES * N if has_bias else 0)

    def per_row(bk: int) -> int:
        # x block + x_scale in, int8 row + scale out (all doubled),
        # int32 accumulators, f32 epilogue temporaries
        return 2 * bk * x_bytes + _COL_BYTES + 2 * N + _COL_BYTES \
            + n_mats * 4 * N + (n_mats + 1) * 4 * N

    def fp(bm: int, bk: int) -> int:
        return fixed(bk) + bm * per_row(bk)

    while block_k > CORE_K and fp(block_m, block_k) > VMEM_BUDGET_BYTES:
        block_k //= 2
    block_m = _fit_rows(M, block_m, per_row(block_k), fixed(block_k))
    return block_m, _fit(K, block_k)


def _apply_activation(x: jax.Array, activation: str | None) -> jax.Array:
    if activation is None:
        return x
    if activation == "gelu":
        return jax.nn.gelu(x, approximate=True)  # tanh approx (paper §III-C)
    if activation == "silu":
        return jax.nn.silu(x)
    if activation == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown epilogue activation {activation!r}")


def _rowquant(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Row absmax int8 quantization of an f32 tile: (q, scale [rows, 1])."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True) + 1e-12
    scale = amax * (1.0 / 127.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


# ---------------------------------------------------------------------------
# Unfused INT8 GEMM (int32 out) — parity baseline + benchmark comparator
# ---------------------------------------------------------------------------
def _cim_gemm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k_steps: int):
    """One (block_m x block_n) output tile; K swept innermost."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # INT8 MACs with INT32 accumulation (the CIM macro's digital adder
    # tree); MXU-friendly dot.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k_step == n_k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def cim_gemm_int8(x: jax.Array, w: jax.Array,
                  block_m: int = 256, block_n: int = 2 * CORE_N,
                  block_k: int = 4 * CORE_K,
                  interpret: bool = False) -> jax.Array:
    """INT8 GEMM: x [M, K] int8 @ w [K, N] int8 -> int32 [M, N].

    Dims must be multiples of the block sizes (ops.py pads).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (K, K2)

    block_m = _fit(M, block_m)
    block_n = _fit(N, block_n)
    block_k = _fit(K, block_k)

    n_k_steps = K // block_k
    grid = (M // block_m, N // block_n, n_k_steps)
    return pl.pallas_call(
        functools.partial(_cim_gemm_kernel, n_k_steps=n_k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda m, n, k: (m, k)),
            pl.BlockSpec((block_k, block_n), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret,
    )(x, w)


# ---------------------------------------------------------------------------
# Row-absmax activation quantization (pre-processing unit)
# ---------------------------------------------------------------------------
def _rowquant_kernel(x_ref, q_ref, s_ref):
    q, scale = _rowquant(x_ref[...].astype(jnp.float32))
    q_ref[...] = q
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def quantize_rows_int8(x: jax.Array, block_m: int = 256,
                       interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Dynamic per-row symmetric int8: x [M, K] -> (q int8, scale f32 [M, 1]).

    M must be a multiple of ``block_m`` after ops.py padding; the full K
    extent sits in one block (the absmax is a row reduction).
    """
    M, K = x.shape
    # full-K row blocks: cap rows in flight so wide rows stay in budget —
    # double-buffered input and int8 output, the scale column, and the
    # body's two f32 row temporaries (the widened row and its quotient)
    block_m = _fit_rows(M, block_m,
                        2 * K * (x.dtype.itemsize + 1) + 2 * _COL_BYTES
                        + 2 * 4 * K)
    grid = (M // block_m,)
    return pl.pallas_call(
        _rowquant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, K), lambda m: (m, 0))],
        out_specs=[
            pl.BlockSpec((block_m, K), lambda m: (m, 0)),
            pl.BlockSpec((block_m, 1), lambda m: (m, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, K), jnp.int8),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)


# ---------------------------------------------------------------------------
# Fused-epilogue INT8 GEMM (MXU + post-processing unit)
# ---------------------------------------------------------------------------
def _cim_gemm_fused_kernel(*refs, n_k_steps: int, activation: str | None,
                           has_bias: bool, has_residual: bool,
                           quantize_out: bool):
    x_ref, w_ref, xs_ref, ws_ref = refs[:4]
    i = 4
    b_ref = None
    if has_bias:
        b_ref, i = refs[i], i + 1
    r_ref = None
    if has_residual:
        r_ref, i = refs[i], i + 1
    out_refs, acc_ref = refs[i:-1], refs[-1]
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k_step == n_k_steps - 1)
    def _epilogue():
        # Post-processing unit: dequantize in VMEM — the int32
        # accumulator never reaches HBM.
        out = acc_ref[...].astype(jnp.float32) * xs_ref[...] * ws_ref[...]
        if has_bias:
            out = out + b_ref[...]
        out = _apply_activation(out, activation)
        if has_residual:
            # Fused residual add (the VPU leg of the post-processing
            # unit): the projection output never exists without it.
            out = out + r_ref[...].astype(jnp.float32)
        if quantize_out:
            q, scale = _rowquant(out)
            out_refs[0][...] = q
            out_refs[1][...] = scale
        else:
            out_refs[0][...] = out.astype(out_refs[0].dtype)


@functools.partial(jax.jit, static_argnames=(
    "activation", "out_dtype", "quantize_out", "block_m", "block_n",
    "block_k", "interpret"))
def cim_gemm_int8_fused(x: jax.Array, w: jax.Array, x_scale: jax.Array,
                        w_scale: jax.Array, bias: jax.Array | None = None,
                        residual: jax.Array | None = None,
                        activation: str | None = None,
                        out_dtype=jnp.float32, quantize_out: bool = False,
                        block_m: int = 256, block_n: int = 2 * CORE_N,
                        block_k: int = 4 * CORE_K,
                        interpret: bool = False):
    """INT8 GEMM with fused dequant/bias/activation/residual epilogue.

    x [M, K] int8 @ w [K, N] int8, rescaled by ``x_scale [M, 1]`` and
    ``w_scale [1, N]`` at the last K-step -> [M, N] ``out_dtype``; or,
    with ``quantize_out``, -> (q int8 [M, N], scale f32 [M, 1]) ready for
    the next GEMM.  ``residual [M, N]`` is added after the activation
    (the transformer-block skip connection, fused so the projection
    output never round-trips to HBM).  Dims must be multiples of the
    block sizes (ops.py pads); ``quantize_out`` forces a single N block
    and excludes ``residual``.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (K, K2)
    assert x_scale.shape == (M, 1), x_scale.shape
    assert w_scale.shape == (1, N), w_scale.shape
    assert not (quantize_out and residual is not None), \
        "residual epilogue is for the block output, not a requantized mid"

    if quantize_out:
        block_n = N
        block_m, block_k = _fit_qout_blocks(M, K, N, block_m, block_k,
                                            n_mats=1,
                                            has_bias=bias is not None)
    else:
        block_m = _fit(M, block_m)
        block_k = _fit(K, block_k)
        block_n = _fit(N, block_n)

    n_k_steps = K // block_k
    grid = (M // block_m, N // block_n, n_k_steps)

    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda m, n, k: (m, k)),
        pl.BlockSpec((block_k, block_n), lambda m, n, k: (k, n)),
        pl.BlockSpec((block_m, 1), lambda m, n, k: (m, 0)),
        pl.BlockSpec((1, block_n), lambda m, n, k: (0, n)),
    ]
    operands = [x, w, x_scale, w_scale]
    if bias is not None:
        assert bias.shape == (1, N), bias.shape
        in_specs.append(pl.BlockSpec((1, block_n), lambda m, n, k: (0, n)))
        operands.append(bias)
    if residual is not None:
        assert residual.shape == (M, N), (residual.shape, (M, N))
        in_specs.append(
            pl.BlockSpec((block_m, block_n), lambda m, n, k: (m, n)))
        operands.append(residual)

    if quantize_out:
        out_specs = [
            pl.BlockSpec((block_m, block_n), lambda m, n, k: (m, n)),
            pl.BlockSpec((block_m, 1), lambda m, n, k: (m, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((M, N), jnp.int8),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
        ]
    else:
        out_specs = pl.BlockSpec((block_m, block_n), lambda m, n, k: (m, n))
        out_shape = jax.ShapeDtypeStruct((M, N), out_dtype)

    return pl.pallas_call(
        functools.partial(_cim_gemm_fused_kernel, n_k_steps=n_k_steps,
                          activation=activation, has_bias=bias is not None,
                          has_residual=residual is not None,
                          quantize_out=quantize_out),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Quantize-in-kernel fused GEMM: pre-processing unit folded into the GEMM
# ---------------------------------------------------------------------------
def _cim_gemm_fused_qin_kernel(*refs, activation: str | None, has_bias: bool,
                               has_residual: bool):
    x_ref, w_ref, ws_ref = refs[:3]
    i = 3
    b_ref = None
    if has_bias:
        b_ref, i = refs[i], i + 1
    r_ref = None
    if has_residual:
        r_ref, i = refs[i], i + 1
    out_ref = refs[i]

    # Pre-processing unit inlined: the full K extent sits in this block,
    # so the row absmax is local and the int8 activations never exist
    # outside the kernel.
    x_q, x_s = _rowquant(x_ref[...].astype(jnp.float32))
    acc = jax.lax.dot_general(x_q, w_ref[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * x_s * ws_ref[...]
    if has_bias:
        out = out + b_ref[...]
    out = _apply_activation(out, activation)
    if has_residual:
        out = out + r_ref[...].astype(jnp.float32)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "activation", "out_dtype", "block_m", "block_n", "interpret"))
def cim_gemm_int8_fused_qin(x: jax.Array, w: jax.Array, w_scale: jax.Array,
                            bias: jax.Array | None = None,
                            residual: jax.Array | None = None,
                            activation: str | None = None,
                            out_dtype=jnp.float32, block_m: int = 256,
                            block_n: int = 2 * CORE_N,
                            interpret: bool = False) -> jax.Array:
    """Fully fused quantized linear as **one** dispatch.

    x [M, K] f32/bf16 is row-quantized *inside* the kernel (full-K
    blocks; callers guard with ``MAX_FUSED_QUANT_K``), multiplied against
    w [K, N] int8, and rescaled/biased/activated (+ optional residual)
    before anything leaves VMEM — the software image of the paper's
    pre-processing unit -> CIM macro -> post-processing unit pipeline
    with no inter-stage HBM traffic at all.  Used for the attention
    QKV and output projections, where a single weight matrix consumes
    the activation stream (the gated-MLP front half keeps a separate
    quantize dispatch: its two-accumulator kernel has no VMEM headroom
    for the f32 activation block).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (K, K2)
    assert w_scale.shape == (1, N), w_scale.shape

    block_n = _fit(N, block_n)
    out_bytes = jnp.dtype(out_dtype).itemsize
    # double-buffered weight/scale/bias blocks; per row the full-K
    # activation block (doubled) with its f32 copy and int8 quantization,
    # the output (+ residual) block, and the f32/int32 epilogue values
    fixed = 2 * K * block_n + _ROW_BYTES * block_n * (1 + (bias is not None))
    per_row = 2 * K * x.dtype.itemsize + 5 * K + 2 * block_n * out_bytes \
        + (2 * block_n * residual.dtype.itemsize if residual is not None
           else 0) + 3 * 4 * block_n
    block_m = _fit_rows(M, block_m, per_row, fixed)
    grid = (M // block_m, N // block_n)

    in_specs = [
        pl.BlockSpec((block_m, K), lambda m, n: (m, 0)),
        pl.BlockSpec((K, block_n), lambda m, n: (0, n)),
        pl.BlockSpec((1, block_n), lambda m, n: (0, n)),
    ]
    operands = [x, w, w_scale]
    if bias is not None:
        assert bias.shape == (1, N), bias.shape
        in_specs.append(pl.BlockSpec((1, block_n), lambda m, n: (0, n)))
        operands.append(bias)
    if residual is not None:
        assert residual.shape == (M, N), (residual.shape, (M, N))
        in_specs.append(pl.BlockSpec((block_m, block_n), lambda m, n: (m, n)))
        operands.append(residual)

    return pl.pallas_call(
        functools.partial(_cim_gemm_fused_qin_kernel, activation=activation,
                          has_bias=bias is not None,
                          has_residual=residual is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda m, n: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Fused gated-MLP front half: act(x @ Wg) * (x @ Wu) in one dispatch
# ---------------------------------------------------------------------------
def _cim_gated_kernel(x_ref, wg_ref, wu_ref, xs_ref, gs_ref, us_ref, *refs,
                      n_k_steps: int, activation: str, quantize_out: bool):
    out_refs = refs[:-2]
    acc_g_ref, acc_u_ref = refs[-2:]
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_g_ref[...] = jnp.zeros_like(acc_g_ref)
        acc_u_ref[...] = jnp.zeros_like(acc_u_ref)

    dims = (((1,), (0,)), ((), ()))
    x = x_ref[...]
    acc_g_ref[...] += jax.lax.dot_general(
        x, wg_ref[...], dims, preferred_element_type=jnp.int32)
    acc_u_ref[...] += jax.lax.dot_general(
        x, wu_ref[...], dims, preferred_element_type=jnp.int32)

    @pl.when(k_step == n_k_steps - 1)
    def _epilogue():
        xs = xs_ref[...]
        g = acc_g_ref[...].astype(jnp.float32) * xs * gs_ref[...]
        u = acc_u_ref[...].astype(jnp.float32) * xs * us_ref[...]
        h = _apply_activation(g, activation) * u
        if quantize_out:
            q, scale = _rowquant(h)
            out_refs[0][...] = q
            out_refs[1][...] = scale
        else:
            out_refs[0][...] = h.astype(out_refs[0].dtype)


@functools.partial(jax.jit, static_argnames=(
    "activation", "out_dtype", "quantize_out", "block_m", "block_n",
    "block_k", "interpret"))
def cim_gated_gemm_int8(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                        x_scale: jax.Array, gate_scale: jax.Array,
                        up_scale: jax.Array, activation: str = "gelu",
                        out_dtype=jnp.float32, quantize_out: bool = False,
                        block_m: int = 256, block_n: int = 2 * CORE_N,
                        block_k: int = 4 * CORE_K,
                        interpret: bool = False):
    """Fused gated-MLP front half: ``act(x@Wg) * (x@Wu)`` in one kernel.

    The gate and up projections share the int8 activation stream; both
    int32 accumulators live in VMEM scratch and the gating product is
    formed in the epilogue.  With ``quantize_out`` the hidden state is
    re-quantized in-epilogue, so the down projection consumes int8
    directly and the f32 hidden state never reaches HBM either.
    """
    M, K = x.shape
    K2, N = w_gate.shape
    assert K == K2 and w_up.shape == (K, N), (x.shape, w_gate.shape,
                                              w_up.shape)
    assert x_scale.shape == (M, 1), x_scale.shape
    assert gate_scale.shape == (1, N) and up_scale.shape == (1, N)

    if quantize_out:
        block_n = N
        block_m, block_k = _fit_qout_blocks(M, K, N, block_m, block_k,
                                            n_mats=2)
    else:
        block_m = _fit(M, block_m)
        block_k = _fit(K, block_k)
        block_n = _fit(N, block_n)

    n_k_steps = K // block_k
    grid = (M // block_m, N // block_n, n_k_steps)

    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda m, n, k: (m, k)),
        pl.BlockSpec((block_k, block_n), lambda m, n, k: (k, n)),
        pl.BlockSpec((block_k, block_n), lambda m, n, k: (k, n)),
        pl.BlockSpec((block_m, 1), lambda m, n, k: (m, 0)),
        pl.BlockSpec((1, block_n), lambda m, n, k: (0, n)),
        pl.BlockSpec((1, block_n), lambda m, n, k: (0, n)),
    ]
    if quantize_out:
        out_specs = [
            pl.BlockSpec((block_m, block_n), lambda m, n, k: (m, n)),
            pl.BlockSpec((block_m, 1), lambda m, n, k: (m, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((M, N), jnp.int8),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
        ]
    else:
        out_specs = pl.BlockSpec((block_m, block_n), lambda m, n, k: (m, n))
        out_shape = jax.ShapeDtypeStruct((M, N), out_dtype)

    return pl.pallas_call(
        functools.partial(_cim_gated_kernel, n_k_steps=n_k_steps,
                          activation=activation, quantize_out=quantize_out),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32),
                        pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret,
    )(x, w_gate, w_up, x_scale, gate_scale, up_scale)


# ---------------------------------------------------------------------------
# Grouped-expert fused GEMMs: expert index as a grid dimension
# ---------------------------------------------------------------------------
def _scalar_im(scalar: bool):
    """Index-map adapter for scalar-prefetch grids: with ``scalar`` the
    grouped kernels' index maps receive the trailing prefetch refs (the
    skip list, and the tile groups), which plain (e, m, n, k) maps must
    ignore."""
    def im(f):
        return (lambda e, m, n, k, *refs: f(e, m, n, k)) if scalar else f
    return im


def _weight_im(scalar: bool, ragged: bool, n_n: int, n_k: int, f):
    """Index map of a per-expert operand block ``f(e, k, n)``: expert
    ``e``'s, or with ``ragged`` the expert ``groups[e]`` whose rows tile
    ``e`` holds.  An empty ragged tile keeps the last (k, n) block, so
    after the tile before it the pipeline issues no weight DMA for it."""
    if not ragged:
        return _scalar_im(scalar)(lambda e, m, n, k: f(e, k, n))

    def im(e, m, n, k, c, g):
        live = c[e] > 0
        return f(g[e], jnp.where(live, k, n_k - 1),
                 jnp.where(live, n, n_n - 1))
    return im


def _grouped_specs(block_m: int, block_n: int, block_k: int,
                   scalar: bool = False, ragged: bool = False,
                   n_n: int = 1, n_k: int = 1):
    """BlockSpecs for (x [E,M,K], w [E,K,N], x_scale [E,M,1],
    w_scale [E,1,N]) with the expert (or ragged row tile) index as the
    leading grid dim.  ``scalar``: index maps take the trailing
    scalar-prefetch refs (the skip list; with ``ragged`` also the tile
    groups)."""
    im = _scalar_im(scalar)
    return [
        pl.BlockSpec((1, block_m, block_k), im(lambda e, m, n, k: (e, m, k))),
        pl.BlockSpec((1, block_k, block_n),
                     _weight_im(scalar, ragged, n_n, n_k,
                                lambda e, k, n: (e, k, n))),
        pl.BlockSpec((1, block_m, 1), im(lambda e, m, n, k: (e, m, 0))),
        pl.BlockSpec((1, 1, block_n),
                     _weight_im(scalar, ragged, n_n, n_k,
                                lambda e, k, n: (e, 0, n))),
    ]


def _grouped_call(kernel, grid, in_specs, out_specs, out_shape,
                  scratch_shapes, operands, counts, interpret, groups=None):
    """Dispatch a grouped kernel, with the per-expert ``counts`` skip
    list (and, ragged, the tile ``groups``) as scalar-prefetch operands
    when given (empty experts or tiles skip all MXU work in their grid
    cells)."""
    if counts is None:
        return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                              out_specs=out_specs, out_shape=out_shape,
                              scratch_shapes=scratch_shapes,
                              interpret=interpret)(*operands)
    prefetch = [counts.astype(jnp.int32)]
    if groups is not None:
        prefetch.append(groups.astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch_shapes)
    return pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret)(*prefetch, *operands)


def _cim_grouped_gemm_kernel(*refs, n_k_steps: int, activation: str | None,
                             has_bias: bool, quantize_out: bool,
                             has_counts: bool, ragged: bool = False):
    """One (expert, block_m x block_n) output tile; K swept innermost.

    With ``has_counts`` the leading ref is the scalar-prefetch skip
    list: experts whose capacity buffers received no tokens skip the
    int8 dot products entirely (no MXU work).  The shared epilogue then
    runs on the zero accumulator — exactly what the full pipeline
    produces for all-zero rows (zero-row activations quantize to q=0),
    so skipping is bit-identical, just cheaper.  ``ragged``: a second
    prefetch ref (the tile groups) follows, read only by index maps.
    """
    if has_counts:
        c_ref, refs = refs[0], refs[1 + ragged:]
    x_ref, w_ref, xs_ref, ws_ref = refs[:4]
    i = 4
    b_ref = None
    if has_bias:
        b_ref, i = refs[i], i + 1
    out_refs, acc_ref = refs[i:-1], refs[-1]
    k_step = pl.program_id(3)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accumulate():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    if has_counts:
        pl.when(c_ref[pl.program_id(0)] > 0)(_accumulate)
    else:
        _accumulate()

    @pl.when(k_step == n_k_steps - 1)
    def _epilogue():
        out = acc_ref[...].astype(jnp.float32) * xs_ref[0] * ws_ref[0]
        if has_bias:
            out = out + b_ref[0]
        out = _apply_activation(out, activation)
        if quantize_out:
            q, scale = _rowquant(out)
            out_refs[0][...] = q[None]
            out_refs[1][...] = scale[None]
        else:
            out_refs[0][...] = out.astype(out_refs[0].dtype)[None]


@functools.partial(jax.jit, static_argnames=(
    "activation", "out_dtype", "quantize_out", "block_m", "block_n",
    "block_k", "interpret"))
def cim_grouped_gemm_int8(x: jax.Array, w: jax.Array, x_scale: jax.Array,
                          w_scale: jax.Array, bias: jax.Array | None = None,
                          counts: jax.Array | None = None,
                          groups: jax.Array | None = None,
                          activation: str | None = None,
                          out_dtype=jnp.float32, quantize_out: bool = False,
                          block_m: int = 256, block_n: int = 2 * CORE_N,
                          block_k: int = 4 * CORE_K,
                          interpret: bool = False):
    """Grouped-expert fused INT8 GEMM — ONE dispatch for all E experts.

    x [E, M, K] int8 @ w [E, K, N] int8, rescaled per expert by
    ``x_scale [E, M, 1]`` and ``w_scale [E, 1, N]`` (+ optional
    ``bias [E, 1, N]``, + gelu/silu/relu) at the last K-step ->
    [E, M, N] ``out_dtype``; or, with ``quantize_out``, ->
    (q int8 [E, M, N], scale f32 [E, M, 1]) ready for the next grouped
    GEMM.  The expert index is the leading grid dimension, so the kernel
    visits each expert's weight stack exactly like ``cim_gemm_int8_fused``
    visits a single weight — weight-stationary within the (e, m, n) tile,
    int32 accumulator in VMEM scratch, nothing intermediate in HBM.
    Per-expert dims must be uniform (ops.py pads the stacked buffers);
    ``quantize_out`` forces a single N block (cross-N row reduction).

    ``counts`` (int32 [E], scalar-prefetched) is the zero-capacity skip
    list: grid cells of experts with ``counts[e] == 0`` run no MXU dot
    products (their all-zero capacity rows previously streamed through
    the MXU anyway); outputs stay bit-identical.

    Ragged form: with ``groups`` (int32 [T], needs ``counts``), x is [T,
    M, K] row tiles and tile t multiplies expert ``groups[t]``'s weights
    (w [E, K, N] for any E); ``counts[t] == 0`` marks an empty tile,
    which also fetches no weights (:func:`_weight_im`).
    """
    E, M, K = x.shape
    E2, K2, N = w.shape
    ragged = groups is not None
    assert K == K2 and (ragged or E == E2), (x.shape, w.shape)
    assert not ragged or (counts is not None and groups.shape == (E,))
    assert x_scale.shape == (E, M, 1), x_scale.shape
    assert w_scale.shape == (E2, 1, N), w_scale.shape

    if quantize_out:
        block_n = N
        block_m, block_k = _fit_qout_blocks(M, K, N, block_m, block_k,
                                            n_mats=1,
                                            has_bias=bias is not None)
    else:
        block_m = _fit(M, block_m)
        block_k = _fit(K, block_k)
        block_n = _fit(N, block_n)

    n_k_steps = K // block_k
    grid = (E, M // block_m, N // block_n, n_k_steps)

    scalar = counts is not None
    in_specs = _grouped_specs(block_m, block_n, block_k, scalar=scalar,
                              ragged=ragged, n_n=grid[2], n_k=n_k_steps)
    im = _scalar_im(scalar)
    operands = [x, w, x_scale, w_scale]
    if bias is not None:
        assert bias.shape == (E2, 1, N), bias.shape
        in_specs.append(pl.BlockSpec(
            (1, 1, block_n), _weight_im(scalar, ragged, grid[2], n_k_steps,
                                        lambda e, k, n: (e, 0, n))))
        operands.append(bias)

    if quantize_out:
        out_specs = [
            pl.BlockSpec((1, block_m, block_n),
                         im(lambda e, m, n, k: (e, m, n))),
            pl.BlockSpec((1, block_m, 1), im(lambda e, m, n, k: (e, m, 0))),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((E, M, N), jnp.int8),
            jax.ShapeDtypeStruct((E, M, 1), jnp.float32),
        ]
    else:
        out_specs = pl.BlockSpec((1, block_m, block_n),
                                 im(lambda e, m, n, k: (e, m, n)))
        out_shape = jax.ShapeDtypeStruct((E, M, N), out_dtype)

    return _grouped_call(
        functools.partial(_cim_grouped_gemm_kernel, n_k_steps=n_k_steps,
                          activation=activation, has_bias=bias is not None,
                          quantize_out=quantize_out, has_counts=scalar,
                          ragged=ragged),
        grid, in_specs, out_specs, out_shape,
        [pltpu.VMEM((block_m, block_n), jnp.int32)],
        operands, counts, interpret, groups)


def _cim_grouped_gated_kernel(*refs, n_k_steps: int, activation: str,
                              quantize_out: bool, has_counts: bool,
                              ragged: bool = False):
    if has_counts:
        c_ref, refs = refs[0], refs[1 + ragged:]
    x_ref, wg_ref, wu_ref, xs_ref, gs_ref, us_ref = refs[:6]
    refs = refs[6:]
    out_refs = refs[:-2]
    acc_g_ref, acc_u_ref = refs[-2:]
    k_step = pl.program_id(3)

    @pl.when(k_step == 0)
    def _init():
        acc_g_ref[...] = jnp.zeros_like(acc_g_ref)
        acc_u_ref[...] = jnp.zeros_like(acc_u_ref)

    def _accumulate():
        dims = (((1,), (0,)), ((), ()))
        x = x_ref[0]
        acc_g_ref[...] += jax.lax.dot_general(
            x, wg_ref[0], dims, preferred_element_type=jnp.int32)
        acc_u_ref[...] += jax.lax.dot_general(
            x, wu_ref[0], dims, preferred_element_type=jnp.int32)

    # zero-capacity skip list: empty experts run no MXU work; their
    # epilogue on the zero accumulators equals the full pipeline on
    # all-zero rows bit-for-bit (zero rows quantize to q=0).
    if has_counts:
        pl.when(c_ref[pl.program_id(0)] > 0)(_accumulate)
    else:
        _accumulate()

    @pl.when(k_step == n_k_steps - 1)
    def _epilogue():
        xs = xs_ref[0]
        g = acc_g_ref[...].astype(jnp.float32) * xs * gs_ref[0]
        u = acc_u_ref[...].astype(jnp.float32) * xs * us_ref[0]
        h = _apply_activation(g, activation) * u
        if quantize_out:
            q, scale = _rowquant(h)
            out_refs[0][...] = q[None]
            out_refs[1][...] = scale[None]
        else:
            out_refs[0][...] = h.astype(out_refs[0].dtype)[None]


@functools.partial(jax.jit, static_argnames=(
    "activation", "out_dtype", "quantize_out", "block_m", "block_n",
    "block_k", "interpret"))
def cim_grouped_gated_gemm_int8(x: jax.Array, w_gate: jax.Array,
                                w_up: jax.Array, x_scale: jax.Array,
                                gate_scale: jax.Array, up_scale: jax.Array,
                                counts: jax.Array | None = None,
                                groups: jax.Array | None = None,
                                activation: str = "gelu",
                                out_dtype=jnp.float32,
                                quantize_out: bool = False,
                                block_m: int = 256, block_n: int = 2 * CORE_N,
                                block_k: int = 4 * CORE_K,
                                interpret: bool = False):
    """Grouped-expert gated front half: ``act(x@Wg) * (x@Wu)`` for all E
    experts in ONE dispatch.

    x [E, M, K] int8 against stacked w_gate/w_up [E, K, N] int8 with
    per-expert scales (``x_scale [E, M, 1]``, ``gate_scale``/``up_scale``
    [E, 1, N]); both int32 accumulators live in VMEM scratch and the
    gating product is formed in the epilogue.  With ``quantize_out`` the
    hidden state is re-quantized in-epilogue, so the grouped down GEMM
    consumes int8 directly — a full MoE expert layer is then exactly
    three dispatches (quantize + this + grouped down) independent of E.
    ``counts`` (int32 [E], scalar-prefetched) skips both dot products
    for zero-capacity experts; outputs stay bit-identical.  ``groups``:
    the ragged form of :func:`cim_grouped_gemm_int8`.
    """
    E, M, K = x.shape
    E2, K2, N = w_gate.shape
    ragged = groups is not None
    assert K == K2 and w_up.shape == (E2, K, N) and (ragged or E == E2), \
        (x.shape, w_gate.shape, w_up.shape)
    assert not ragged or (counts is not None and groups.shape == (E,))
    assert x_scale.shape == (E, M, 1), x_scale.shape
    assert gate_scale.shape == (E2, 1, N) and up_scale.shape == (E2, 1, N)

    if quantize_out:
        block_n = N
        block_m, block_k = _fit_qout_blocks(M, K, N, block_m, block_k,
                                            n_mats=2)
    else:
        block_m = _fit(M, block_m)
        block_k = _fit(K, block_k)
        block_n = _fit(N, block_n)

    n_k_steps = K // block_k
    grid = (E, M // block_m, N // block_n, n_k_steps)

    scalar = counts is not None
    im = _scalar_im(scalar)

    def wim(f):
        return _weight_im(scalar, ragged, grid[2], n_k_steps, f)

    in_specs = [
        pl.BlockSpec((1, block_m, block_k), im(lambda e, m, n, k: (e, m, k))),
        pl.BlockSpec((1, block_k, block_n), wim(lambda e, k, n: (e, k, n))),
        pl.BlockSpec((1, block_k, block_n), wim(lambda e, k, n: (e, k, n))),
        pl.BlockSpec((1, block_m, 1), im(lambda e, m, n, k: (e, m, 0))),
        pl.BlockSpec((1, 1, block_n), wim(lambda e, k, n: (e, 0, n))),
        pl.BlockSpec((1, 1, block_n), wim(lambda e, k, n: (e, 0, n))),
    ]
    if quantize_out:
        out_specs = [
            pl.BlockSpec((1, block_m, block_n),
                         im(lambda e, m, n, k: (e, m, n))),
            pl.BlockSpec((1, block_m, 1), im(lambda e, m, n, k: (e, m, 0))),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((E, M, N), jnp.int8),
            jax.ShapeDtypeStruct((E, M, 1), jnp.float32),
        ]
    else:
        out_specs = pl.BlockSpec((1, block_m, block_n),
                                 im(lambda e, m, n, k: (e, m, n)))
        out_shape = jax.ShapeDtypeStruct((E, M, N), out_dtype)

    return _grouped_call(
        functools.partial(_cim_grouped_gated_kernel, n_k_steps=n_k_steps,
                          activation=activation, quantize_out=quantize_out,
                          has_counts=scalar, ragged=ragged),
        grid, in_specs, out_specs, out_shape,
        [pltpu.VMEM((block_m, block_n), jnp.int32),
         pltpu.VMEM((block_m, block_n), jnp.int32)],
        [x, w_gate, w_up, x_scale, gate_scale, up_scale], counts, interpret,
        groups)
