"""Multi-head Latent Attention (DeepSeek-V3 / arXiv:2412.19437).

One parameter set, three execution paths:

* no cache (training, the plain forward) -- latents are up-projected to
  per-head K/V and fed to the standard attention path.
* ring cache -- the *absorbed* form in jnp over per-row latent buffers.
* paged cache (the serving engine) -- each token stores its latent
  ``c_kv`` (``kv_lora_rank``) and rope key (``qk_rope_head_dim``) side
  by side in a block pool, int8 with a per-token scale each, through the
  engine's block tables.  Decode runs the absorbed form in the Pallas kernel
  ``mla_decode_paged``: queries are folded through W_UK so scores are
  ``q_lat . c_kv + q_rope . k_rope``, the output ``attn . c_kv`` is
  folded through W_UV.  Chunked prefill runs the same absorbed form in
  ``mla_prefill_paged``, a tile of the chunk's queries at a time over
  the row's blocks up to the tile's last position: at 128 heads it does
  about the work of up-projecting the context to per-head K/V (the form
  DeepSeek uses for prefill) and, unlike it, moves neither K/V nor a
  score matrix through HBM.

YaRN (``rope_factor`` > 1) rescales the rope frequencies and, through
``mscale_all_dim``, the softmax temperature, as published.

Under a :class:`~repro.quant.plan.QuantPlan` covering ``mla_proj`` the
query and KV down-projections run as ONE wide fused int8 GEMM
(``"down"``, split after) and the query up-projection as another
(``q_up``, stored flat); ``mla_out`` puts the out-projection on the
fused pipeline with the block residual in its epilogue.  ``kv_up`` (W_UK / W_UV) stays
bf16: decode folds it around the latent kernel.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .attention import (NEG_INF, _paged_update, _quantize_kv,
                        blockwise_attention, dense_attention)
from .layers import (Param, apply_rope, linear_param, rmsnorm_apply,
                     rmsnorm_init, yarn_frequencies, yarn_get_mscale)

@dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # YaRN rope scaling (1.0: plain rope)
    rope_factor: float = 1.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        s = 1.0 / math.sqrt(self.qk_head_dim)
        if self.rope_factor > 1.0 and self.mscale_all_dim:
            m = yarn_get_mscale(self.rope_factor, self.mscale_all_dim)
            s *= m * m
        return s


def mla_rope(x: jax.Array, positions: jax.Array, cfg: MLAConfig,
             theta: float) -> jax.Array:
    """Rope on [..., S, heads, qk_rope_head_dim], YaRN when scaled."""
    if cfg.rope_factor <= 1.0:
        return apply_rope(x, positions, theta)
    freqs = yarn_frequencies(cfg.qk_rope_head_dim, theta, cfg.rope_factor,
                             cfg.original_max_position, cfg.beta_fast,
                             cfg.beta_slow)
    scale = (yarn_get_mscale(cfg.rope_factor, cfg.mscale)
             / yarn_get_mscale(cfg.rope_factor, cfg.mscale_all_dim))
    return apply_rope(x, positions, theta, freqs=freqs, scale=scale)


def mla_init(key, d_model: int, n_heads: int, cfg: MLAConfig,
             dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 6)
    nope, rope, vdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "q_down": linear_param(ks[0], d_model, (cfg.q_lora_rank,),
                               ("fsdp", None), dtype),
        "q_norm": rmsnorm_init(cfg.q_lora_rank),
        "q_up": linear_param(ks[1], cfg.q_lora_rank, (n_heads, nope + rope),
                             (None, "heads", None), dtype),
        # kv_down emits [c_kv (kv_lora) | k_rope (rope)] in one projection
        "kv_down": linear_param(ks[2], d_model, (cfg.kv_lora_rank + rope,),
                                ("fsdp", None), dtype),
        "kv_norm": rmsnorm_init(cfg.kv_lora_rank),
        "kv_up": linear_param(ks[3], cfg.kv_lora_rank, (n_heads, nope + vdim),
                              (None, "heads", None), dtype),
        "o": Param(
            linear_param(ks[4], n_heads * vdim, (d_model,), (), dtype)
            .value.reshape(n_heads, vdim, d_model),
            ("heads", None, "fsdp")),
    }


def _quantized(params) -> bool:
    from repro.quant.linear import QuantizedLinear  # local: no cycle
    return isinstance(params.get("down"), QuantizedLinear)


def _project(params, x, cfg: MLAConfig, positions, rope_theta):
    """x [B, S, d] -> q_nope [B,S,H,nope], q_rope [B,S,H,rope], c_kv
    [B,S,r] (normed latent), k_rope [B,S,rope] (shared rope key)."""
    from repro.quant.linear import quantized_matmul  # local: no cycle
    r = cfg.kv_lora_rank
    if _quantized(params):
        # q_down | kv_down as ONE wide fused int8 GEMM, split after
        wide = quantized_matmul(x, params["down"], use_kernel=None).astype(
            x.dtype)
        cq, ckv = wide[..., :cfg.q_lora_rank], wide[..., cfg.q_lora_rank:]
        cq = rmsnorm_apply(params["q_norm"], cq)
        q = quantized_matmul(cq, params["q_up"], use_kernel=None).astype(
            x.dtype)
        q = q.reshape(*cq.shape[:-1], -1, cfg.qk_head_dim)
    else:
        cq = rmsnorm_apply(params["q_norm"],
                           jnp.einsum("bsd,dr->bsr", x, params["q_down"]))
        q = jnp.einsum("bsr,rhk->bshk", cq, params["q_up"])
        ckv = jnp.einsum("bsd,dr->bsr", x, params["kv_down"])
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = mla_rope(q[..., cfg.qk_nope_head_dim:], positions, cfg,
                      rope_theta)
    c_kv = rmsnorm_apply(params["kv_norm"], ckv[..., :r])
    k_rope = mla_rope(ckv[..., r:][:, :, None, :], positions, cfg,
                      rope_theta)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def _out_proj(params, out, x, residual):
    """out [B, S, H, v] -> [B, S, d] (+ residual, in the fused epilogue
    on the quantized path)."""
    from repro.quant.linear import QuantizedLinear, quantized_out_proj
    o_w = params["o"]
    if isinstance(o_w, QuantizedLinear):
        return quantized_out_proj(o_w, out, residual=residual).astype(x.dtype)
    o = jnp.einsum("bshv,hvd->bsd", out.astype(x.dtype), o_w)
    return o if residual is None else residual + o


def mla_apply(
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: MLAConfig,
    *,
    rope_theta: float = 10000.0,
    cache: Optional[dict] = None,
    residual: Optional[jax.Array] = None,
) -> tuple[jax.Array, Optional[dict]]:
    """x [B, S, d] -> (out [B, S, d] (+ ``residual``), new cache)."""
    B, S, _ = x.shape
    nope = cfg.qk_nope_head_dim
    scale = cfg.softmax_scale
    with jax.named_scope("mla.proj"):
        q_nope, q_rope, c_kv, k_rope = _project(params, x, cfg, positions,
                                                rope_theta)
    H = q_nope.shape[2]
    w_uk = params["kv_up"][..., :nope]          # [r, H, nope]
    w_uv = params["kv_up"][..., nope:]          # [r, H, v]

    if cache is not None and "block_tables" in cache:
        out, new_cache = _paged_apply(cache, q_nope, q_rope, c_kv, k_rope,
                                      positions, w_uk, w_uv, scale)
        with jax.named_scope("mla.out"):
            return _out_proj(params, out, x, residual), new_cache

    if cache is None:
        # Materialized path: standard MHA over up-projected K/V.  The
        # softmax scale (YaRN's mscale**2 included) is folded into q.
        with jax.named_scope("mla.attend"):
            kv = jnp.einsum("bsr,rhk->bshk", c_kv, params["kv_up"])
            k_nope, v = kv[..., :nope], kv[..., nope:]
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                          (B, S, H, cfg.qk_rope_head_dim))],
                axis=-1)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            q = (q * (scale * math.sqrt(cfg.qk_head_dim))).astype(q.dtype)
            if S <= 2048:
                out = dense_attention(q, k, v, positions, positions, "causal")
            else:
                out = blockwise_attention(q, k, v, positions, positions,
                                          "causal")
        with jax.named_scope("mla.out"):
            return _out_proj(params, out, x, residual), None

    # ------------------------------------------------------------------
    # Ring cache, absorbed form in jnp: score/value against the latents.
    # ------------------------------------------------------------------
    idx = cache["index"]                 # [B] per-slot indices
    c_cache = jax.vmap(
        lambda b, n, i: jax.lax.dynamic_update_slice(b, n, (i, 0)))(
        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), idx)
    r_cache = jax.vmap(
        lambda b, n, i: jax.lax.dynamic_update_slice(b, n, (i, 0)))(
        cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), idx)
    new_cache = {"c_kv": c_cache, "k_rope": r_cache, "index": idx + S}

    # Fold queries through W_uk: q_lat [B, S, H, r]
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, w_uk)
    scores = (
        jnp.einsum("bshr,btr->bhst", q_lat.astype(jnp.float32),
                   c_cache.astype(jnp.float32))
        + jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32),
                     r_cache.astype(jnp.float32))
    ) * scale
    t_pos = jnp.arange(c_cache.shape[1])[None, None, None, :]
    valid = t_pos <= positions[:, None, :, None]
    valid &= t_pos < (idx[:, None, None, None] + S)
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhst,btr->bshr", probs.astype(c_cache.dtype), c_cache)
    out = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv)
    return _out_proj(params, out, x, residual), new_cache


# ---------------------------------------------------------------------------
# Paged latent cache
# ---------------------------------------------------------------------------
def _paged_apply(cache, q_nope, q_rope, c_kv, k_rope, positions, w_uk,
                 w_uv, scale):
    """Latent write + attend for one layer of the paged cache.

    ``cache`` holds the scan group's stacked pools (``latent_pages`` [L,
    NB, bs, W]: each token's latent and rope key side by side, zero-
    padded to W; ``c_scale_pages`` / ``r_scale_pages`` [L, NB, bs]:
    their per-token scales), this layer's index ``layer`` and per-row
    ``block_tables`` / ``index``.  Token p of a row lives at ``[layer,
    block_tables[p // bs], p % bs]``; positions are implicit (a row's
    positions below its write index are all written), so no position
    pool is kept.  Returns (out [B, S, H, v], new cache without
    ``layer``).
    """
    idx = cache["index"]
    bt = cache["block_tables"]
    layer = cache["layer"]
    S = positions.shape[1]
    valid_len = jnp.sum(positions < 2 ** 29, axis=1).astype(jnp.int32)
    int8 = cache["latent_pages"].dtype == jnp.int8

    def write(name, new):
        return _paged_update(cache[name], new, bt, idx, layer, valid_len)

    with jax.named_scope("mla.cache_write"):
        if int8:
            # per token: the latent and the rope key each get a scale
            cq, cs = _quantize_kv(c_kv)
            rq, rs = _quantize_kv(k_rope)
        else:
            cq, rq = c_kv, k_rope
            cs = rs = jnp.ones(c_kv.shape[:-1], jnp.float32)
        row = jnp.concatenate([cq, rq], axis=-1)
        row = jnp.pad(row, ((0, 0), (0, 0),
                            (0, cache["latent_pages"].shape[-1]
                             - row.shape[-1])))
        pools = {"latent_pages": write("latent_pages", row),
                 "c_scale_pages": write("c_scale_pages", cs),
                 "r_scale_pages": write("r_scale_pages", rs)}
    new_cache = {**pools, "block_tables": bt, "index": idx + S}

    if S == 1:
        with jax.named_scope("mla.decode"):
            q_lat = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0],
                               w_uk).astype(q_nope.dtype)
            o_lat = mla_decode(q_lat, q_rope[:, 0], pools, bt,
                               positions[:, 0], layer, scale)
            out = jnp.einsum("bhr,rhv->bhv", o_lat.astype(w_uv.dtype),
                             w_uv)[:, None]
        return out, new_cache

    with jax.named_scope("mla.prefill"):
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope,
                           w_uk).astype(q_nope.dtype)
        o_lat = mla_prefill(q_lat, q_rope, pools, bt, positions, layer,
                            scale)
        out = jnp.einsum("bshr,rhv->bshv", o_lat.astype(w_uv.dtype), w_uv)
    return out, new_cache


def mla_decode(q_lat, q_rope, pools, bt, q_pos, layer, scale):
    """One-token absorbed decode over layer ``layer`` of the latent
    pools: the Pallas kernel on the chip, its jnp oracle otherwise
    (``quant.linear``'s kernel resolution).  q_lat [B, H, r]; q_rope
    [B, H, rope]; q_pos [B] (rows at the empty sentinel attend nothing
    and read zeros).  Returns [B, H, r]."""
    from repro.kernels import ops as kops
    from repro.kernels.ref import mla_decode_paged_ref
    from repro.quant.linear import _resolve_use_kernel
    args = (q_lat, q_rope, pools["latent_pages"], pools["c_scale_pages"],
            pools["r_scale_pages"], bt, q_pos, layer)
    if _resolve_use_kernel(None):
        return kops.mla_decode_paged(*args, scale=scale)
    return mla_decode_paged_ref(*args, scale=scale)


def mla_prefill(q_lat, q_rope, pools, bt, positions, layer, scale):
    """Causal absorbed attention of a prefill chunk over layer ``layer``
    of the latent pools (the chunk's latents already written): the
    Pallas kernel on the chip, its jnp oracle otherwise.  q_lat [B, S, H,
    r]; q_rope [B, S, H, rope]; positions [B, S] (pads at the empty
    sentinel read zeros).  Returns [B, S, H, r]."""
    from repro.kernels import ops as kops
    from repro.kernels.ref import mla_prefill_paged_ref
    from repro.quant.linear import _resolve_use_kernel
    args = (q_lat, q_rope, pools["latent_pages"], pools["c_scale_pages"],
            pools["r_scale_pages"], bt, positions, layer)
    if _resolve_use_kernel(None):
        return kops.mla_prefill_paged(*args, scale=scale)
    return mla_prefill_paged_ref(*args, scale=scale)


def init_paged_latent_cache(batch: int, num_blocks: int, block_size: int,
                            max_blocks: int, cfg: MLAConfig,
                            dtype=jnp.int8) -> dict:
    """Paged latent state: a shared block pool holding each token's
    latent and rope key side by side, zero-padded to a multiple of 128
    (int8, or bf16 with unit scales; with a lane-aligned minor dim XLA
    stores the pool row-major, as the kernel reads it, instead of
    transposing it and relaying it out every layer), a per-token scale
    pool for each of the two, ending in the block's positions so the
    kernel reads them lane-dense, plus per-row block tables.  Block 0 is
    the reserved null block the allocator never hands out."""
    width = -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128
    return {
        "latent_pages": jnp.zeros((num_blocks, block_size, width), dtype),
        "c_scale_pages": jnp.zeros((num_blocks, block_size), jnp.float32),
        "r_scale_pages": jnp.zeros((num_blocks, block_size), jnp.float32),
        "block_tables": jnp.zeros((batch, max_blocks), jnp.int32),
        "index": jnp.zeros((batch,), jnp.int32),
    }


def paged_latent_cache_logical_axes() -> dict:
    """Pools are replicated (data-parallel attention); tables and
    indices are per-row host state."""
    return {
        "latent_pages": (None, None, None),
        "c_scale_pages": (None, None),
        "r_scale_pages": (None, None),
        "block_tables": ("batch", None),
        "index": ("batch",),
    }


def init_mla_cache(batch: int, max_len: int, cfg: MLAConfig,
                   dtype=jnp.bfloat16) -> dict:
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
        "index": jnp.zeros((batch,), jnp.int32),
    }


def mla_cache_logical_axes() -> dict:
    # latent cache is sharded over sequence for long-context decode
    # (context parallelism) — the resolver maps "kv_seq" appropriately.
    return {
        "c_kv": ("batch", "kv_seq", None),
        "k_rope": ("batch", "kv_seq", None),
        "index": ("batch",),
    }
