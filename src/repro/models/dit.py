"""Diffusion Transformer (DiT) with adaLN conditioning on the fused INT8
CIM pipeline — the paper's second workload class (DiT-XL/2, Table III).

Structure (Peebles & Xie, arXiv:2212.09748, adaLN-Zero variant):
patchify -> linear patch embed -> timestep/label embedding -> N DiT
blocks -> adaLN final layer -> unpatchify.  Each block is

    mod                  = adaLN(c) -> 6*d (shift/scale/gate for attn+mlp)
    x += gate_msa * attn(modulate(ln(x), shift_msa, scale_msa))
    x += gate_mlp * mlp (modulate(ln(x), shift_mlp, scale_mlp))

with parameter-free LayerNorms (the modulation supplies scale/shift).
Non-autoregressive: full bidirectional attention over a fixed token grid
(1024 tokens for XL/2 at 512x512), no KV cache, no RoPE — the GEMM-dense
regime where the paper reports up to 33.8% latency improvement on the
CIM-MXU (Design B).

Every weight GEMM a :class:`~repro.quant.plan.QuantPlan` covers runs the
SAME fused quantized apply sites as the LLM stack: the wide QKV
projection (``quantized_qkv_proj``), the attention out-projection
(``quantized_out_proj``), the non-gated MLP (``quantized_mlp_apply``),
and — new with the ``adaln`` plan kind — the adaLN modulation GEMM
(``quantized_matmul`` with the bias folded into the fused epilogue).  A
full-plan DiT block is exactly **6** Pallas dispatches (1 adaLN + 1 QKV
+ 1 out-proj + 3 MLP), structurally pinned in tests/test_diffusion.py;
because the N blocks scan over stacked params, a whole-model denoise
step traces those same 6 kernels.  The block's gated residual
(``x + gate * out``) multiplies the branch output before the add, so —
unlike the LLM block — the skip connection cannot ride the GEMM
epilogue; it stays a VPU elementwise op, exactly how the simulator's
``dit_block_ops`` accounts it (OpKind.CONDITIONING / ELEMENTWISE).

Deviation from the training-time recipe: adaLN-Zero initializes the
modulation projection (and final layer) to zero so blocks start as
identities; an inference reproduction with random weights would then be
the identity function end to end, so init here uses the same
truncated-normal scale as every other projection.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.quant.linear import (QuantizedLinear, quantize_attention,
                                quantize_linear, quantize_mlp,
                                quantized_matmul)
from . import attention as attn_mod
from .layers import (Param, linear_param, mlp_apply, mlp_init, param_axes,
                     param_values, scale_param, truncated_normal_init)


@dataclass(frozen=True)
class DiTConfig:
    """Shape of a DiT: depth/width plus the latent-patch geometry."""

    name: str
    n_layers: int                 # depth (XL/2: 28)
    d_model: int                  # hidden size (XL/2: 1152)
    n_heads: int                  # attention heads (XL/2: 16)
    patch_size: int = 2           # latent patchification (the "/2")
    in_channels: int = 4          # VAE latent channels
    input_size: int = 64          # latent spatial extent (512px / 8 VAE)
    mlp_ratio: int = 4
    n_classes: int = 1000         # ImageNet; +1 null class for CFG
    learn_sigma: bool = True      # predict (eps, sigma); samplers use eps
    freq_dim: int = 256           # sinusoidal timestep embedding width
    activation: str = "gelu"      # non-gated MLP (DiT uses GELU-tanh)
    param_dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_ff(self) -> int:
        return self.mlp_ratio * self.d_model

    @property
    def tokens(self) -> int:
        return (self.input_size // self.patch_size) ** 2

    @property
    def out_channels(self) -> int:
        return self.in_channels * (2 if self.learn_sigma else 1)

    @property
    def null_class(self) -> int:
        """The classifier-free-guidance null label (last table row)."""
        return self.n_classes

    def param_count(self) -> int:
        """Approximate parameter count (sanity checks)."""
        d, L = self.d_model, self.n_layers
        per_block = 4 * d * d + 2 * d * self.d_ff + 6 * d * (d + 1)
        p2c = self.patch_size ** 2 * self.in_channels
        return int(L * per_block + p2c * d + self.freq_dim * d + d * d
                   + (self.n_classes + 1) * d
                   + 2 * d * (d + 1)
                   + d * self.patch_size ** 2 * self.out_channels)


def _dtype(cfg: DiTConfig):
    return jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Patchify / timestep embedding primitives
# ---------------------------------------------------------------------------
def patchify(x: jax.Array, patch: int) -> jax.Array:
    """Latents [B, C, H, W] -> patch tokens [B, (H/p)*(W/p), p*p*C]."""
    B, C, H, W = x.shape
    p = patch
    x = x.reshape(B, C, H // p, p, W // p, p)
    x = x.transpose(0, 2, 4, 3, 5, 1)             # B, H/p, W/p, p, p, C
    return x.reshape(B, (H // p) * (W // p), p * p * C)


def unpatchify(tokens: jax.Array, patch: int, channels: int,
               size: int) -> jax.Array:
    """Inverse of :func:`patchify`: [B, T, p*p*C] -> [B, C, H, W]."""
    B = tokens.shape[0]
    p, g = patch, size // patch
    x = tokens.reshape(B, g, g, p, p, channels)
    x = x.transpose(0, 5, 1, 3, 2, 4)             # B, C, g, p, g, p
    return x.reshape(B, channels, size, size)


def timestep_embedding(t: jax.Array, dim: int,
                       max_period: float = 10000.0) -> jax.Array:
    """Sinusoidal timestep features: t [B] -> [B, dim] f32."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) *
                    jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def _ln(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Parameter-free LayerNorm (adaLN supplies scale/shift)."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def _modulate(x: jax.Array, shift: jax.Array, scale: jax.Array) -> jax.Array:
    """adaLN modulation: x [B, T, d], shift/scale [B, d]."""
    return x * (1.0 + scale[:, None, :]) + shift[:, None, :]


def adaln_apply(params: dict, c: jax.Array, n_chunks: int) -> list[jax.Array]:
    """adaLN modulation head: SiLU(c) -> Linear(d, n_chunks*d) -> split.

    When the plan covers ``adaln`` the kernel is a
    :class:`QuantizedLinear` and the GEMM runs the fused INT8 pipeline
    in ONE quantize-in-kernel dispatch, bias folded into the epilogue
    (the paper's post-processing unit); otherwise a bf16 einsum.
    """
    h = jax.nn.silu(c.astype(jnp.float32))
    w = params["kernel"]
    # the named scope tags the GEMM (and its kernel) in compiled HLO
    with jax.named_scope("adaln"):
        if isinstance(w, QuantizedLinear):
            out = quantized_matmul(h, w, use_kernel=None,
                                   bias=params["bias"])
        else:
            out = h.astype(w.dtype) @ w + params["bias"]
    out = out.astype(jnp.float32)
    return jnp.split(out, n_chunks, axis=-1)


# ---------------------------------------------------------------------------
# DiT block
# ---------------------------------------------------------------------------
def dit_block_init(key, cfg: DiTConfig) -> dict:
    dtype = _dtype(cfg)
    ka, km, kc = jax.random.split(key, 3)
    return {
        "attn": attn_mod.attention_init(ka, cfg.d_model, cfg.n_heads,
                                        cfg.n_heads, cfg.head_dim, dtype),
        "mlp": mlp_init(km, cfg.d_model, cfg.d_ff, cfg.activation, dtype),
        "adaln": {
            "kernel": linear_param(kc, cfg.d_model, (6 * cfg.d_model,),
                                   ("fsdp", None), dtype),
            "bias": scale_param(6 * cfg.d_model, (None,), value=0.0),
        },
    }


def dit_block_apply(params: dict, x: jax.Array, c: jax.Array,
                    cfg: DiTConfig, positions: jax.Array) -> jax.Array:
    """One DiT block: x [B, T, d], c [B, d] -> [B, T, d].

    Full bidirectional attention (``mask_kind="full"``, no RoPE, no
    cache); QuantPlan-covered projections dispatch the fused INT8
    pipeline through the same apply sites as the LLM block.  The gated
    residuals stay elementwise (the gate multiplies the branch before
    the add, so it cannot ride the out-projection epilogue).
    """
    (shift_msa, scale_msa, gate_msa,
     shift_mlp, scale_mlp, gate_mlp) = adaln_apply(params["adaln"], c, 6)
    dt = x.dtype

    h = _modulate(_ln(x), shift_msa.astype(dt), scale_msa.astype(dt))
    attn_out, _ = attn_mod.attention_apply(
        params["attn"], h, positions, mask_kind="full", use_rope=False)
    x = x + gate_msa[:, None, :].astype(dt) * attn_out

    h = _modulate(_ln(x), shift_mlp.astype(dt), scale_mlp.astype(dt))
    mlp_out = mlp_apply(params["mlp"], h, cfg.activation).astype(dt)
    return x + gate_mlp[:, None, :].astype(dt) * mlp_out


def quantize_dit_block(params: dict, plan) -> dict:
    """Rewrite one block's weights per the plan's DiT coverage
    (``DIT_LAYER_KINDS``); norms-free, so only projections change.
    Idempotent: already-quantized leaves pass through."""
    out = dict(params)
    if (plan.covers("attn_qkv") or plan.covers("attn_out")):
        out["attn"] = quantize_attention(out["attn"],
                                         qkv=plan.covers("attn_qkv"),
                                         out=plan.covers("attn_out"))
    if plan.covers("mlp"):
        out["mlp"] = quantize_mlp(out["mlp"])
    if plan.covers("adaln") and not isinstance(out["adaln"]["kernel"],
                                               QuantizedLinear):
        out["adaln"] = {"kernel": quantize_linear(out["adaln"]["kernel"]),
                        "bias": out["adaln"]["bias"]}
    return out


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------
class DiTModel:
    """adaLN DiT assembly mirroring :class:`repro.models.model.Model`:
    pure-functional params, scanned identical blocks, plan-driven INT8.

    Entry points:
        init(key)                 -> param values tree
        forward(params, x, t, y)  -> model output [B, out_ch, H, W]
        quantize(params, plan, mesh=) -> QuantizedLinear tree (sharded)
    """

    def __init__(self, cfg: DiTConfig):
        self.cfg = cfg

    # -- parameters ------------------------------------------------------
    def _head_tree(self, keys) -> dict:
        cfg = self.cfg
        dtype = _dtype(cfg)
        p2c = cfg.patch_size ** 2 * cfg.in_channels
        return {
            "patch_embed": {
                "kernel": linear_param(keys[0], p2c, (cfg.d_model,),
                                       ("fsdp", None), dtype),
                "bias": scale_param(cfg.d_model, (None,), value=0.0),
            },
            "t_embed": {
                "w1": linear_param(keys[1], cfg.freq_dim, (cfg.d_model,),
                                   ("fsdp", None), dtype),
                "b1": scale_param(cfg.d_model, (None,), value=0.0),
                "w2": linear_param(keys[2], cfg.d_model, (cfg.d_model,),
                                   ("fsdp", None), dtype),
                "b2": scale_param(cfg.d_model, (None,), value=0.0),
            },
            "y_embed": {
                "table": Param(
                    truncated_normal_init(keys[3],
                                          (cfg.n_classes + 1, cfg.d_model),
                                          dtype, 0.02),
                    ("vocab", "fsdp")),
            },
            "final": {
                "adaln": {
                    "kernel": linear_param(keys[4], cfg.d_model,
                                           (2 * cfg.d_model,),
                                           ("fsdp", None), dtype),
                    "bias": scale_param(2 * cfg.d_model, (None,), value=0.0),
                },
                "linear": {
                    "kernel": linear_param(
                        keys[5], cfg.d_model,
                        (cfg.patch_size ** 2 * cfg.out_channels,),
                        ("fsdp", None), dtype),
                    "bias": scale_param(
                        cfg.patch_size ** 2 * cfg.out_channels, (None,),
                        value=0.0),
                },
            },
        }

    def init(self, key):
        """Concrete parameter values; blocks stacked on a leading layers
        axis (one scan body, like Model's layer groups)."""
        cfg = self.cfg

        def build(k):
            keys = jax.random.split(k, 7)
            p = param_values(self._head_tree(keys))
            bkeys = jax.random.split(keys[6], cfg.n_layers)
            p["blocks"] = jax.vmap(
                lambda bk: param_values(dit_block_init(bk, cfg)))(bkeys)
            return p

        return jax.jit(build)(key)

    def param_axes(self):
        """Logical sharding axes matching the init tree."""
        box: dict = {}

        def capture(key):
            keys = jax.random.split(key, 7)
            p = self._head_tree(keys)
            p["blocks"] = dit_block_init(keys[6], self.cfg)
            box["axes"] = param_axes(p)
            return param_values(p)

        jax.eval_shape(capture, jax.random.PRNGKey(0))
        axes = box["axes"]
        axes["blocks"] = jax.tree.map(
            lambda a: ("layers", *a) if isinstance(a, tuple) else a,
            axes["blocks"], is_leaf=lambda a: isinstance(a, tuple))
        return axes

    def abstract_params(self):
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return shapes, self.param_axes()

    # -- forward ----------------------------------------------------------
    def conditioning(self, params, t: jax.Array, y: jax.Array) -> jax.Array:
        """Timestep + label embedding: (t [B], y [B] int) -> c [B, d]."""
        te = params["t_embed"]
        h = timestep_embedding(t, self.cfg.freq_dim)
        h = jax.nn.silu(h.astype(jnp.float32) @ te["w1"].astype(jnp.float32)
                        + te["b1"])
        h = h @ te["w2"].astype(jnp.float32) + te["b2"]
        ye = jnp.take(params["y_embed"]["table"], y, axis=0)
        return (h + ye.astype(jnp.float32)).astype(_dtype(self.cfg))

    def forward(self, params, x: jax.Array, t: jax.Array,
                y: jax.Array) -> jax.Array:
        """One denoise evaluation: latents x [B, C, H, W], timesteps
        t [B], labels y [B] -> [B, out_channels, H, W]."""
        cfg = self.cfg
        dtype = _dtype(cfg)
        c = self.conditioning(params, t, y)
        pe = params["patch_embed"]
        tok = patchify(x.astype(dtype), cfg.patch_size)
        tok = tok @ pe["kernel"] + pe["bias"].astype(dtype)
        B, T, _ = tok.shape
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

        def body(carry, lparams):
            return dit_block_apply(lparams, carry, c, cfg, pos), None

        tok, _ = jax.lax.scan(body, tok, params["blocks"])

        fin = params["final"]
        shift, scale = adaln_apply(fin["adaln"], c, 2)
        h = _modulate(_ln(tok), shift.astype(dtype), scale.astype(dtype))
        out = h @ fin["linear"]["kernel"] + fin["linear"]["bias"].astype(dtype)
        return unpatchify(out.astype(jnp.float32), cfg.patch_size,
                          cfg.out_channels, cfg.input_size)

    # -- serving-side weight quantization ---------------------------------
    def quantize(self, params, plan=None, mesh=None, rules=None):
        """Rewrite block weights per the plan's DiT coverage
        (adaln/attn_qkv/attn_out/mlp -> :class:`QuantizedLinear`).  The
        patch embed, timestep/label embedders, and final layer stay bf16
        (the <1% head/frontend work, same accounting as the LM head).

        ``mesh`` device_puts the tree for tensor-parallel serving: q and
        scale co-shard on the output-channel axis, QKV column-parallel /
        out-proj and MLP down row-parallel, exactly the LLM placement.
        """
        from repro.quant.plan import FULL_INT8
        plan = FULL_INT8 if plan is None else plan
        out = dict(params)
        out["blocks"] = jax.vmap(
            lambda b: quantize_dit_block(b, plan))(params["blocks"])
        if mesh is not None:
            from repro.parallel.sharding import make_shardings
            axes = self._plan_axes(plan)
            out = jax.device_put(out, make_shardings(mesh, out, axes, rules))
        return out

    def _plan_axes(self, plan):
        """Logical-axes tree matching the tree :meth:`quantize` builds."""
        from repro.quant.plan import attn_plan_axes, mlp_plan_axes, \
            q_scale_axes
        axes = self.param_axes()
        blocks = dict(axes["blocks"])
        if plan.covers("attn_qkv") or plan.covers("attn_out"):
            blocks["attn"] = attn_plan_axes(blocks["attn"],
                                            qkv=plan.covers("attn_qkv"),
                                            out=plan.covers("attn_out"))
        if plan.covers("mlp"):
            blocks["mlp"] = mlp_plan_axes(blocks["mlp"])
        if plan.covers("adaln"):
            blocks["adaln"] = {
                "kernel": q_scale_axes(blocks["adaln"]["kernel"]),
                "bias": blocks["adaln"]["bias"]}
        axes["blocks"] = blocks
        return axes


@functools.lru_cache(maxsize=32)
def build_dit(cfg: DiTConfig) -> DiTModel:
    return DiTModel(cfg)
