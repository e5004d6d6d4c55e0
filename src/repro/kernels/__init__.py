"""Pallas TPU kernels (validated on CPU via interpret=True).

kernels/<name>.py : pl.pallas_call + BlockSpec implementations
ops.py            : jit'd wrappers (padding, quant epilogues, dispatch)
ref.py            : pure-jnp oracles

Kernels cover the compute hot-spots the paper optimizes: INT8
weight-stationary GEMM (CIM-MXU mode), decode-GEMV attention, prefill
flash attention, and the SSD chunk scan for the SSM/hybrid assigned
architectures.

Fused INT8 epilogue pipeline (QuantPlan execution)
--------------------------------------------------
The paper's CIM-MXU quantizes activations in a *pre-processing unit*
and rescales/activates in a *post-processing unit* inside the MXU
pipeline — peripheral data movement, not the MACs, dominates CIM LLM
inference cost, so nothing round-trips to HBM between those stages.
The software mirror (cim_gemm.py):

* ``quantize_rows_int8``       — pre-processing unit: dynamic row-absmax
  activation quantization as one Pallas kernel (was an XLA f32 pass);
* ``cim_gemm_int8_fused``      — MXU + post-processing unit: the int32
  accumulator stays in VMEM scratch and the last K-step applies
  dequant scales, optional bias, gelu/silu, and an optional fused
  **residual** add (the transformer-block skip connection) — with
  ``quantize_out`` it re-quantizes the row block for the next GEMM;
* ``cim_gemm_int8_fused_qin``  — the same pipeline as ONE dispatch: the
  row quantization happens inside the kernel (full-K blocks), so a
  single weight-consuming GEMM (attention QKV / out-projection) never
  emits or reads an intermediate tensor at all;
* ``cim_gated_gemm_int8``      — gated-MLP front half, ``act(gate)*up``
  in the epilogue;
* ``cim_grouped_gemm_int8`` / ``cim_grouped_gated_gemm_int8`` — the same
  fused pipelines batched over a leading **expert** grid dimension:
  stacked ``[E, T, d]`` capacity buffers against stacked ``[E, K, N]``
  int8 weights, one (expert, m, n) output tile per grid cell;
* ``decode_attention`` (decode_attention.py) — flash-decode over the
  ring-buffer KV cache: online softmax streamed over KV blocks, fp or
  **int8 cache dequantized in-kernel** (per-head scale vectors ride
  with the int8 blocks; scales fold outside the dots so the MXU sees
  integer operands), block-skip lists via scalar prefetch, and a
  split-KV variant (partial (o, m, l) per split + a small combine
  dispatch) for long contexts.

Which layers run this pipeline is declared by a ``QuantPlan``
(repro.quant.plan): ``Model.quantize(params, plan)`` rewrites covered
weights into QuantizedLinear leaves, and the layer applies dispatch on
them uniformly.  With the full plan, one decode step of a dense
attention+MLP block is exactly **6** Pallas dispatches — 1 wide QKV
(q/k/v concatenated along the output axis, quantize-in-kernel), 1
flash-decode attention kernel reading the int8 KV cache (``attn_kv``
coverage), 1 out-projection with the residual fused into its epilogue,
and 3 for the gated MLP (quantize, gated GEMM, down GEMM w/ residual)
— previously ~6 bf16 einsums + 5+ XLA elementwise passes with every
intermediate in HBM.

MoE expert compute is a **constant** number of dispatches independent of
the expert count: ``quantized_moe_apply`` runs ONE row-quantize over the
stacked capacity rows, ONE grouped gated GEMM, and ONE grouped down GEMM
(``ops.cim_quantized_grouped_mlp``), with the expert index as a kernel
grid dimension indexing the stacked weight/scale tensors.  A 60-expert
qwen2-moe or 256-expert deepseek-v3 layer traces exactly the same three
kernels as a 4-expert reduced config — the per-expert Python loop this
replaced traced 3·E dispatches (kept as ``quantized_moe_apply_looped``;
tests pin grouped == looped bit-for-bit).  The grouped kernels take an
optional scalar-prefetched ``counts`` skip list: zero-capacity experts
(no tokens routed this step) run no MXU dot products in their grid
cells instead of streaming all-zero rows, bit-identically.  The serving
engine's ``quant_plan=`` turns it on for the decode path.

Tensor parallelism: under a model-axis sharding context the quantized
apply sites shard_map these same kernels per device (repro.quant.tp) —
column-parallel QKV/up/gate, row-parallel out-proj/down via
``ops.cim_int8_gemm_acc`` partial accumulators psum'd before one
epilogue, expert-parallel grouped MoE, and head-parallel flash-decode
attention over the ``model``-sharded KV cache (no collectives — softmax
is per-head) — bit-identical to the unsharded pipeline with per-shard
dispatch counts unchanged.
"""
from . import ops, ref

__all__ = ["ops", "ref"]
