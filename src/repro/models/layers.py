"""Core layer primitives: parameter containers, norms, embeddings, MLPs.

Pure-functional, flax-free module style: every module is an ``init``
function returning a pytree of :class:`Param` leaves (value + logical
sharding axes) and an ``apply`` function consuming the *value* tree.
``jax.eval_shape`` over ``init`` yields allocation-free parameter
skeletons for the multi-pod dry-run.

Logical axis names (resolved by repro.parallel.sharding):
    "vocab"   — vocabulary dim            -> model axis
    "heads"   — attention/ssm head dim    -> model axis
    "kv_heads"— kv head dim               -> model axis (fallback replicate)
    "mlp"     — FFN hidden dim            -> model axis
    "expert"  — MoE expert dim            -> model axis (EP)
    "fsdp"    — parameter shard dim       -> (pod, data) axes (ZeRO-3)
    "layers"  — stacked-layer dim         -> replicated (scan axis)
    None      — replicated
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp


class Param(NamedTuple):
    """A parameter leaf: array (or ShapeDtypeStruct) + logical axes."""

    value: Any
    axes: tuple

    # Treated as a pytree *leaf container* via flatten of value only.


def _is_param(x) -> bool:
    return isinstance(x, Param)


def param_values(tree):
    """Strip Param wrappers -> plain value tree (jit/grads operate here).
    Non-Param leaves (already-stripped values) pass through unchanged."""
    return jax.tree.map(lambda p: p.value if _is_param(p) else p, tree,
                        is_leaf=_is_param)


def param_axes(tree):
    """Strip Param wrappers -> logical-axes tree (None for plain leaves)."""
    return jax.tree.map(lambda p: p.axes if _is_param(p) else None, tree,
                        is_leaf=_is_param)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def truncated_normal_init(key, shape, dtype, scale: float):
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                                jnp.float32)).astype(dtype)


def linear_param(key, in_dim: int, out_shape: Sequence[int], axes: tuple,
                 dtype=jnp.bfloat16, scale: Optional[float] = None) -> Param:
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    shape = (in_dim, *out_shape)
    return Param(truncated_normal_init(key, shape, dtype, scale), axes)


def scale_param(dim: int, axes: tuple = (None,), dtype=jnp.float32,
                value: float = 1.0) -> Param:
    return Param(jnp.full((dim,), value, dtype), axes)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm_init(dim: int) -> dict:
    return {"scale": scale_param(dim)}


def rmsnorm_apply(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"]).astype(dtype)


def layernorm_init(dim: int, bias: bool = False) -> dict:
    p = {"scale": scale_param(dim)}
    if bias:
        p["bias"] = scale_param(dim, value=0.0)
    return p


def layernorm_apply(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    x = x * params["scale"]
    if "bias" in params:
        x = x + params["bias"]
    return x.astype(dtype)


def make_norm(kind: str, dim: int):
    if kind == "rmsnorm":
        return rmsnorm_init(dim), rmsnorm_apply
    if kind == "layernorm":
        return layernorm_init(dim), layernorm_apply
    raise ValueError(f"unknown norm {kind!r}")


def norm_apply(kind: str, params: dict, x: jax.Array) -> jax.Array:
    return rmsnorm_apply(params, x) if kind == "rmsnorm" else \
        layernorm_apply(params, x)


# ---------------------------------------------------------------------------
# Embedding + head
# ---------------------------------------------------------------------------
def embedding_init(key, vocab: int, dim: int, dtype=jnp.bfloat16) -> dict:
    emb = truncated_normal_init(key, (vocab, dim), dtype, 1.0)
    return {"embedding": Param(emb, ("vocab", "fsdp"))}


def embedding_apply(params: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["embedding"], tokens, axis=0)


def embedding_attend(params: dict, x: jax.Array) -> jax.Array:
    """Tied-weight logits: x @ E^T / sqrt(d) (keeps init logits ~unit)."""
    emb = params["embedding"]
    scale = 1.0 / math.sqrt(emb.shape[-1])
    return (jnp.einsum("...d,vd->...v", x, emb) * scale).astype(jnp.float32)


def lm_head_init(key, dim: int, vocab: int, dtype=jnp.bfloat16) -> dict:
    return {"kernel": linear_param(key, dim, (vocab,), ("fsdp", "vocab"),
                                   dtype)}


def lm_head_apply(params: dict, x: jax.Array) -> jax.Array:
    return jnp.einsum("...d,dv->...v", x, params["kernel"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)


def yarn_get_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention-temperature factor (arXiv:2309.00071 §3.4, as
    DeepSeek-V3 publishes it): ``0.1 * mscale * ln(scale) + 1``."""
    return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_frequencies(head_dim: int, theta: float, factor: float,
                     original_max_position: int, beta_fast: float,
                     beta_slow: float) -> jax.Array:
    """YaRN rope frequencies [head_dim/2]: the rotary dims that turn more
    than ``beta_fast`` times over the original context keep their
    frequency, those that turn fewer than ``beta_slow`` times are
    divided by ``factor``, and a linear ramp blends the dims between
    (DeepSeek-V3's ``yarn_find_correction_range`` and
    ``yarn_linear_ramp_mask``)."""
    def correction_dim(rotations: float) -> float:
        return (head_dim * math.log(original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extra = rope_frequencies(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                    # 1: original frequency
    return extra / factor * (1.0 - keep) + extra * keep


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0, freqs: Optional[jax.Array] = None,
               scale: float = 1.0) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq].  ``freqs``
    (default: plain rope at ``theta``) and ``scale`` (multiplies sin and
    cos: YaRN's mscale ratio) override the rotation."""
    head_dim = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(head_dim, theta)              # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    sin = scale * jnp.sin(angles)[..., :, None, :]
    cos = scale * jnp.cos(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (dense FFN; gated variants)
# ---------------------------------------------------------------------------
def mlp_init(key, d_model: int, d_ff: int, activation: str = "gelu",
             dtype=jnp.bfloat16) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    gated = activation in ("geglu", "swiglu")
    p = {
        "up": linear_param(k1, d_model, (d_ff,), ("fsdp", "mlp"), dtype),
        "down": linear_param(k2, d_ff, (d_model,), ("mlp", "fsdp"), dtype),
    }
    if gated:
        p["gate"] = linear_param(k3, d_model, (d_ff,), ("fsdp", "mlp"), dtype)
    return p


def _activate(name: str, x: jax.Array) -> jax.Array:
    if name in ("gelu", "geglu"):
        return jax.nn.gelu(x, approximate=True)  # tanh approx (paper §III-C)
    if name in ("silu", "swiglu"):
        return jax.nn.silu(x)
    if name == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown activation {name!r}")


def mlp_is_quantized(params: dict) -> bool:
    """True if the MLP value tree holds int8 QuantizedLinear leaves."""
    from repro.quant.linear import QuantizedLinear  # local import: no cycle
    return isinstance(params.get("up"), QuantizedLinear)


def mlp_apply(params: dict, x: jax.Array, activation: str = "gelu",
              residual: jax.Array | None = None) -> jax.Array:
    """Dense FFN.  ``residual`` (the block skip connection) is added to
    the output when given; on the quantized path the add is fused into
    the down-projection GEMM's epilogue."""
    from repro.parallel.context import shard  # local import: no cycle
    if mlp_is_quantized(params):
        # INT8 serving path: dispatches the fused Pallas pipeline (one
        # quantize + two fused GEMM kernels) on TPU, its oracle on CPU.
        # The hidden state lives inside the kernel, so the bf16 path's
        # shard(h, "mlp") TP constraint has no tensor to attach to —
        # instead, under a model-axis sharding context the pipeline
        # itself goes tensor-parallel via shard_map (quant/tp.py):
        # up/gate column-parallel, down row-parallel with the psum
        # folded in before the residual epilogue, bit-identical to the
        # unsharded path.
        from repro.quant.linear import quantized_mlp_apply
        return quantized_mlp_apply(params, x, activation, use_kernel=None,
                                   residual=residual)
    hidden_axes = ("batch",) + (None,) * (x.ndim - 2) + ("mlp",)
    up = jnp.einsum("...d,df->...f", x, params["up"])
    if "gate" in params:
        gate = jnp.einsum("...d,df->...f", x, params["gate"])
        h = _activate(activation, gate) * up
    else:
        h = _activate(activation, up)
    h = shard(h, hidden_axes)
    out = jnp.einsum("...f,fd->...d", h, params["down"])
    return out if residual is None else residual + out
