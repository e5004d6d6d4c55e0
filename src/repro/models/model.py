"""Unified causal LM assembly: embeds, grouped-scan block stacks, head.

One :class:`Model` serves every assigned architecture.  Consecutive
identical (mixer, ffn) layers are stacked and scanned (small HLO even at
95 layers); heterogeneous stacks become a handful of scan groups.  All
entry points work with ShapeDtypeStruct params (jax.eval_shape) so the
multi-pod dry-run never allocates.

Entry points:
    init(key)                      -> param values tree
    abstract_params()              -> (shape tree, logical-axes tree)
    loss(params, batch)            -> (scalar, metrics)   [training]
    prefill(params, batch, cache)  -> (logits, cache)
    decode_step(params, batch, cache) -> (logits, cache)
    init_cache(batch, max_len)     -> cache values; cache_axes() to shard
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.context import shard
from . import attention as attn_mod
from . import mla as mla_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from . import xlstm as xlstm_mod
from .layers import (embedding_apply, embedding_attend, embedding_init,
                     linear_param, lm_head_apply, lm_head_init, make_norm,
                     mlp_apply, mlp_init, norm_apply, param_axes,
                     param_values)


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Single block
# ---------------------------------------------------------------------------
def block_init(key, spec: tuple[str, str], cfg: ModelConfig) -> dict:
    mixer, ffn = spec
    dtype = _dtype(cfg)
    km, kf, kn1, kn2 = jax.random.split(key, 4)
    p: dict = {}

    if mixer in ("attn", "attn_local"):
        p["mixer_norm"], _ = make_norm(cfg.norm, cfg.d_model)
        p["attn"] = attn_mod.attention_init(
            km, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            dtype, qk_norm=cfg.qk_norm)
    elif mixer == "mla":
        p["mixer_norm"], _ = make_norm(cfg.norm, cfg.d_model)
        p["mla"] = mla_mod.mla_init(km, cfg.d_model, cfg.n_heads, cfg.mla,
                                    dtype)
    elif mixer == "mamba2":
        p["mixer_norm"], _ = make_norm(cfg.norm, cfg.d_model)
        p["mamba"] = ssm_mod.mamba2_init(km, cfg.d_model, cfg.ssm, dtype)
    elif mixer == "mlstm":
        p["mixer_norm"], _ = make_norm(cfg.norm, cfg.d_model)
        p["mlstm"] = xlstm_mod.mlstm_block_init(km, cfg.d_model, cfg.xlstm,
                                                dtype)
    elif mixer == "slstm":
        p["mixer_norm"], _ = make_norm(cfg.norm, cfg.d_model)
        p["slstm"] = xlstm_mod.slstm_block_init(km, cfg.d_model, cfg.xlstm,
                                                dtype)
    else:
        raise ValueError(f"unknown mixer {mixer!r}")

    if ffn == "dense":
        p["ffn_norm"], _ = make_norm(cfg.norm, cfg.d_model)
        p["mlp"] = mlp_init(kf, cfg.d_model, cfg.d_ff, cfg.activation, dtype)
    elif ffn == "moe":
        p["ffn_norm"], _ = make_norm(cfg.norm, cfg.d_model)
        p["moe"] = moe_mod.moe_init(kf, cfg.d_model, cfg.moe, cfg.activation,
                                    dtype)
    return p


def block_apply(params: dict, spec: tuple[str, str], cfg: ModelConfig,
                x: jax.Array, positions: jax.Array,
                cache: Optional[dict], prefix_len,
                train: bool = False) -> tuple:
    """Returns (x, new_cache, aux_loss, load): ``load`` is the MoE
    layer's held-expert load (:func:`moe.held_load` over the rows with a
    real position: int32 [3]), zeros for other layers."""
    mixer, ffn = spec
    aux = jnp.zeros((), jnp.float32)
    load = jnp.zeros((3,), jnp.int32)

    h = norm_apply(cfg.norm, params["mixer_norm"], x)
    new_cache = None
    if mixer in ("attn", "attn_local"):
        kind = "causal"
        window = None
        if mixer == "attn_local":
            kind, window = "sliding", cfg.sliding_window
        if cfg.frontend == "vision":
            kind = "prefix" if mixer == "attn" else kind
        # The skip connection is handed to the layer: quantized
        # out-projections fuse it into their GEMM epilogue, bf16 layers
        # add it normally — block_apply stays agnostic of which leaves
        # are QuantizedLinear.
        x, new_cache = attn_mod.attention_apply(
            params["attn"], h, positions, mask_kind=kind, window=window,
            prefix_len=prefix_len, rope_theta=cfg.rope_theta, cache=cache,
            residual=x)
    elif mixer == "mla":
        x, new_cache = mla_mod.mla_apply(
            params["mla"], h, positions, cfg.mla, rope_theta=cfg.rope_theta,
            cache=cache, residual=x)
    elif mixer == "mamba2":
        out, new_cache = ssm_mod.mamba2_apply(params["mamba"], h, cfg.ssm,
                                              cache=cache)
    elif mixer == "mlstm":
        out, new_cache = xlstm_mod.mlstm_block_apply(params["mlstm"], h,
                                                     cfg.xlstm, cache=cache)
    elif mixer == "slstm":
        out, new_cache = xlstm_mod.slstm_block_apply(params["slstm"], h,
                                                     cfg.xlstm, cache=cache)
    if mixer not in ("attn", "attn_local", "mla"):
        x = x + out

    if ffn == "dense":
        h = norm_apply(cfg.norm, params["ffn_norm"], x)
        x = mlp_apply(params["mlp"], h, cfg.activation, residual=x)
    elif ffn == "moe":
        h = norm_apply(cfg.norm, params["ffn_norm"], x)
        out, aux, load = moe_mod.moe_layer(
            params["moe"], h, cfg.moe, cfg.activation, train=train,
            valid=positions < 2 ** 29)
        x = x + out
    return x, new_cache, aux, load


def block_cache_init(spec: tuple[str, str], cfg: ModelConfig, batch: int,
                     max_len: int,
                     kv_dtype: Optional[str] = None) -> Optional[dict]:
    mixer, _ = spec
    kv_dtype = kv_dtype or cfg.kv_cache_dtype
    kv_dtype = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    if mixer == "attn":
        return attn_mod.init_kv_cache(batch, max_len, cfg.n_kv_heads,
                                      cfg.head_dim, dtype=kv_dtype)
    if mixer == "attn_local":
        # sliding-window layers never need more than the window
        span = min(max_len, (cfg.sliding_window or max_len))
        return attn_mod.init_kv_cache(batch, span, cfg.n_kv_heads,
                                      cfg.head_dim, dtype=kv_dtype)
    if mixer == "mla":
        return mla_mod.init_mla_cache(batch, max_len, cfg.mla)
    if mixer == "mamba2":
        return ssm_mod.init_ssm_cache(batch, cfg.d_model, cfg.ssm)
    if mixer == "mlstm":
        return xlstm_mod.init_mlstm_cache(batch, cfg.d_model, cfg.xlstm)
    if mixer == "slstm":
        return xlstm_mod.init_slstm_cache(batch, cfg.d_model, cfg.xlstm)
    raise ValueError(mixer)


def block_cache_axes(spec: tuple[str, str],
                     cfg: Optional[ModelConfig] = None,
                     kv_dtype: Optional[str] = None) -> Optional[dict]:
    mixer, _ = spec
    if mixer in ("attn", "attn_local"):
        quant = (kv_dtype or (cfg.kv_cache_dtype if cfg else "")) == "int8"
        return attn_mod.kv_cache_logical_axes(quantized=quant)
    if mixer == "mla":
        return mla_mod.mla_cache_logical_axes()
    if mixer == "mamba2":
        return ssm_mod.ssm_cache_logical_axes()
    if mixer == "mlstm":
        return {"conv": ("batch", None, "mlp"), "C": ("batch", "heads", None, None),
                "n": ("batch", "heads", None), "m": ("batch", "heads"),
                "index": ("batch",)}
    if mixer == "slstm":
        return {"c": ("batch", "heads", None), "n": ("batch", "heads", None),
                "h": ("batch", "heads", None), "m": ("batch", "heads", None),
                "index": ("batch",)}
    raise ValueError(mixer)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------
class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.groups = cfg.layer_groups()

    # -- parameters ------------------------------------------------------
    def _init_head(self, keys) -> dict:
        """Everything outside the layer groups (embedding, final norm,
        LM head, frontend projection), from ``_init_with_axes``'s keys."""
        cfg = self.cfg
        dtype = _dtype(cfg)
        p: dict = {"embed": embedding_init(keys[0], cfg.vocab, cfg.d_model,
                                           dtype)}
        norm_p, _ = make_norm(cfg.norm, cfg.d_model)
        p["final_norm"] = norm_p
        if not cfg.tie_embeddings:
            p["head"] = lm_head_init(keys[1], cfg.d_model, cfg.vocab, dtype)
        if cfg.frontend == "vision" and cfg.frontend_dim:
            p["frontend_proj"] = {
                "kernel": linear_param(keys[2], cfg.frontend_dim,
                                       (cfg.d_model,), ("fsdp", None), dtype)}
        return p

    def _init_with_axes(self, key) -> dict:
        keys = jax.random.split(key, len(self.groups) + 4)
        p = self._init_head(keys)
        for gi, (spec, count) in enumerate(self.groups):
            gkeys = jax.random.split(keys[3 + gi], count)
            stacked = jax.vmap(
                lambda k, spec=spec: param_values(block_init(k, spec, self.cfg))
            )(gkeys)
            p[f"group_{gi}"] = stacked
        return p

    def init(self, key) -> Any:
        """Concrete parameter values (small/smoke configs)."""
        return jax.jit(lambda k: param_values(self._init_with_axes(k)))(key)

    def init_quantized(self, key, plan=None) -> Any:
        """The serving params ``quantize(init(key), plan)`` builds, made
        one layer at a time: each layer is initialised and quantized in
        one jitted call and written into a preallocated stacked tree, so
        the bf16 copies of all layers are never live together (a 4-layer
        deepseek-67b slice holds 5.7 GB of bf16 layer weights against
        the 2.8 GB they quantize to)."""
        from repro.quant.plan import FULL_INT8, apply_plan
        plan = FULL_INT8 if plan is None else plan
        cfg = self.cfg
        keys = jax.random.split(key, len(self.groups) + 4)
        p = jax.jit(lambda ks: param_values(self._init_head(ks)))(keys)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def put(stack, layer, j):
            return jax.tree.map(
                lambda s, x: jax.lax.dynamic_update_slice_in_dim(
                    s, x.astype(s.dtype), j, 0), stack, layer)

        for gi, (spec, count) in enumerate(self.groups):
            @jax.jit
            def one_layer(k, spec=spec):
                layer = jax.vmap(lambda kk: param_values(
                    block_init(kk, spec, cfg)))(k[None])
                return apply_plan([(spec, 1)], {"group_0": layer},
                                  plan)["group_0"]

            gkeys = jax.random.split(keys[3 + gi], count)
            shapes = jax.eval_shape(one_layer, gkeys[0])
            stack = jax.tree.map(
                lambda a: jnp.zeros((count, *a.shape[1:]), a.dtype), shapes)
            for j in range(count):
                stack = put(stack, one_layer(gkeys[j]), j)
            p[f"group_{gi}"] = stack
        return p

    def abstract_params(self):
        """(ShapeDtypeStruct tree, logical-axes tree) — no allocation."""
        shapes = jax.eval_shape(
            lambda k: param_values(self._init_with_axes(k)),
            jax.random.PRNGKey(0))
        axes = self.param_axes()
        return shapes, axes

    def param_axes(self):
        """Logical sharding axes matching the init tree."""
        cfg = self.cfg
        box: dict = {}

        def capture(key):
            p: dict = {"embed": embedding_init(key, cfg.vocab, cfg.d_model)}
            norm_p, _ = make_norm(cfg.norm, cfg.d_model)
            p["final_norm"] = norm_p
            if not cfg.tie_embeddings:
                p["head"] = lm_head_init(key, cfg.d_model, cfg.vocab)
            if cfg.frontend == "vision" and cfg.frontend_dim:
                p["frontend_proj"] = {
                    "kernel": linear_param(key, cfg.frontend_dim,
                                           (cfg.d_model,), ("fsdp", None))}
            for gi, (spec, _) in enumerate(self.groups):
                p[f"group_{gi}"] = block_init(key, spec, cfg)
            box["axes"] = param_axes(p)
            return param_values(p)

        jax.eval_shape(capture, jax.random.PRNGKey(0))
        axes = box["axes"]
        # stacked groups gain a leading "layers" axis
        for gi in range(len(self.groups)):
            g = axes[f"group_{gi}"]
            axes[f"group_{gi}"] = jax.tree.map(
                lambda a: ("layers", *a) if isinstance(a, tuple) else a, g,
                is_leaf=lambda a: isinstance(a, tuple))
        return axes

    # -- forward ----------------------------------------------------------
    def _embed_inputs(self, params, batch) -> tuple[jax.Array, Any]:
        cfg = self.cfg
        prefix_len = None
        if cfg.frontend == "audio":
            x = batch["frame_embeddings"].astype(_dtype(cfg))
        elif cfg.frontend == "vision":
            prefix_len = cfg.frontend_len
            if "patch_embeddings" in batch:
                img = batch["patch_embeddings"].astype(_dtype(cfg))
                if "frontend_proj" in params:
                    img = jnp.einsum("bpd,de->bpe", img,
                                     params["frontend_proj"]["kernel"])
                txt = embedding_apply(params["embed"], batch["inputs"])
                x = jnp.concatenate([img, txt], axis=1)
                prefix_len = img.shape[1]
            else:
                # text-only continuation (decode): the image prefix is
                # already in the cache; its length still shapes the mask.
                x = embedding_apply(params["embed"], batch["inputs"])
        else:
            x = embedding_apply(params["embed"], batch["inputs"])
        return shard(x, ("batch", "act_seq", None)), prefix_len

    def _stack(self, params, x, positions, caches, prefix_len,
               decode: bool = False, train: bool = False):
        """Run all layer groups. caches: None or dict group_i -> stacked.

        Each group is one ``jax.lax.scan`` over its stacked layers, with
        the layer params and the layer caches as ``xs`` and the new
        caches as ``ys``.  A paged cache group (it holds
        ``block_tables``) is split: its block pools (``*_pages``, [L, NB,
        ...]) ride the scan's carry with the layer index, and each layer
        scatters into and reads the stacked pools at that index
        (``attention._paged_cache_apply``), so XLA updates the donated
        pools in place instead of slicing each layer's pool out and
        writing it back whole.  Its per-row block tables and write index
        stay in ``xs``/``ys``.  Ring caches, recurrent state and the
        cache-less forward carry no pools.

        Returns (x, new caches, summed aux loss, held-expert loads: int32
        [n_moe_layers, 3], one row per MoE layer in depth order).
        """
        cfg = self.cfg
        total_aux = jnp.zeros((), jnp.float32)
        new_caches = {} if caches is not None else None
        loads = []

        for gi, (spec, count) in enumerate(self.groups):
            gparams = params[f"group_{gi}"]
            gcache = caches[f"group_{gi}"] if caches is not None else None
            pools = {}
            if gcache is not None and "block_tables" in gcache:
                pools = {k: a for k, a in gcache.items()
                         if k.endswith("_pages")}
                gcache = {k: a for k, a in gcache.items() if k not in pools}

            def body(carry, layer_in, spec=spec):
                x, aux, pools, layer = carry
                x = shard(x, ("batch", "act_seq", None))
                lparams, lcache = layer_in
                if pools:
                    lcache = {**lcache, **pools, "layer": layer}
                x, ncache, a, load = block_apply(lparams, spec, cfg, x,
                                                 positions, lcache,
                                                 prefix_len, train)
                x = shard(x, ("batch", "act_seq", None))
                if pools:
                    pools = {k: ncache[k] for k in pools}
                    ncache = {k: a for k, a in ncache.items()
                              if k not in pools}
                return (x, aux + a, pools, layer + 1), (ncache, load)

            if cfg.remat and not decode:
                body = jax.checkpoint(body)

            (x, total_aux, pools, _), (ncache, load) = jax.lax.scan(
                body, (x, total_aux, pools, jnp.zeros((), jnp.int32)),
                (gparams, gcache))
            if new_caches is not None:
                new_caches[f"group_{gi}"] = {**ncache, **pools}
            if spec[1] == "moe":
                loads.append(load)
        loads = (jnp.concatenate(loads) if loads
                 else jnp.zeros((0, 3), jnp.int32))
        return x, new_caches, total_aux, loads

    def _head(self, params, x):
        if self.cfg.tie_embeddings:
            return embedding_attend(params["embed"], x)
        return lm_head_apply(params["head"], x)

    def forward(self, params, batch, caches=None, positions=None,
                decode: bool = False, head: bool = True,
                last_only: bool = False, last_index=None,
                train: bool = False):
        """(logits or features, new caches, aux loss)."""
        return self._forward(params, batch, caches, positions, decode, head,
                             last_only, last_index, train)[:3]

    def _forward(self, params, batch, caches=None, positions=None,
                 decode: bool = False, head: bool = True,
                 last_only: bool = False, last_index=None,
                 train: bool = False):
        cfg = self.cfg
        x, prefix_len = self._embed_inputs(params, batch)
        B, S = x.shape[:2]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        x, new_caches, aux, loads = self._stack(params, x, positions, caches,
                                                prefix_len, decode, train)
        x = norm_apply(cfg.norm, params["final_norm"], x)
        if last_only:
            x = x[:, -1:]
        elif last_index is not None:
            # per-row gather of one position (bucket-padded prefill: the
            # last *real* token, not the last padded slot)
            x = jax.vmap(
                lambda xi, i: jax.lax.dynamic_slice_in_dim(xi, i, 1, 0)
            )(x, last_index.astype(jnp.int32))
        if not head:
            return x, new_caches, aux, loads
        logits = shard(self._head(params, x), ("batch", "act_seq", "vocab"))
        return logits, new_caches, aux, loads

    # -- training ----------------------------------------------------------
    LOSS_CHUNK_BUDGET = 2 ** 26   # logits elements per chunk (global)

    def _nll(self, params, feats, targets, mask):
        logits = self._head(params, feats).astype(jnp.float32)
        logits = shard(logits, ("batch", "act_seq", "vocab"))
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        if mask is not None:
            return jnp.sum(nll * mask), jnp.sum(mask)
        return jnp.sum(nll), jnp.asarray(nll.size, jnp.float32)

    def loss(self, params, batch):
        """Cross entropy with *sequence-chunked* head: the [B, S, vocab]
        logits tensor is never materialized for large S x vocab (e.g.
        command-r 256k vocab x 1M tokens); each chunk is rematerialized in
        the backward pass (jax.checkpoint)."""
        cfg = self.cfg
        # training keeps the MoE capacity path (models/moe.py)
        feats, _, aux = self.forward(params, batch, head=False, train=True)
        targets = batch["targets"]
        if cfg.frontend == "vision":
            feats = feats[:, -targets.shape[1]:]
        mask = batch.get("loss_mask")
        B, S, _ = feats.shape

        # pick a chunk count that divides S and bounds chunk logits size
        n_chunks = 1
        while (S % (n_chunks * 2) == 0 and
               B * (S // n_chunks) * cfg.vocab > self.LOSS_CHUNK_BUDGET):
            n_chunks *= 2

        if n_chunks == 1:
            total, count = self._nll(params, feats, targets, mask)
        else:
            C = S // n_chunks
            fc = feats.reshape(B, n_chunks, C, -1).swapaxes(0, 1)
            tc = targets.reshape(B, n_chunks, C).swapaxes(0, 1)
            mc = (mask.reshape(B, n_chunks, C).swapaxes(0, 1)
                  if mask is not None else
                  jnp.ones((n_chunks, B, C), jnp.float32))

            # checkpoint with *explicit* args (no tracer closure): the
            # per-chunk logits are rematerialized in backward.
            nll_ckpt = jax.checkpoint(
                lambda p, f, t, mk: self._nll(p, f, t, mk))

            def chunk_fn(carry, xs):
                f, t, mk = xs
                s, c = nll_ckpt(params, f, t, mk)
                return (carry[0] + s, carry[1] + c), None

            init = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
            (total, count), _ = jax.lax.scan(chunk_fn, init, (fc, tc, mc))

        loss = total / jnp.maximum(count, 1.0)
        total_loss = loss + aux
        return total_loss, {"nll": loss, "aux": aux, "tokens": count}

    # -- serving -------------------------------------------------------------
    def prefill(self, params, batch, caches):
        logits, caches, _ = self.forward(params, batch, caches=caches)
        return logits, caches

    def prefill_last(self, params, batch, caches):
        """Prefill returning only the last position's logits (the serving
        path — avoids materializing [B, S, vocab] at 32k context)."""
        logits, caches, _ = self.forward(params, batch, caches=caches,
                                         last_only=True)
        return logits, caches

    def prefill_padded(self, params, batch, caches, lengths, offset=None):
        """Prefill bucket-padded prompts without leaking pad tokens.

        ``lengths`` (int32 [B]) are the true prompt lengths; positions at
        or beyond them get the empty-slot sentinel (2**30), so the pad
        entries written into the KV cache are masked exactly like empty
        slots and generations never condition on them.  Returns logits at
        each row's last *real* token ([B, 1, vocab]) and caches whose
        write index is reset to the true length — the next decode token
        lands at position ``length``, overwriting the first pad slot.

        ``offset`` (int32 [B], default zeros) starts each row's
        positions at ``offset[b]`` instead of 0 — chunked prefill: the
        continuously-batched paged engine feeds a long prompt through
        this entry one chunk at a time, with ``lengths`` the valid
        length *within the chunk* and the write index resuming at
        ``offset + lengths``.
        """
        B = self._batch_size(batch)
        S = self._step_len(batch)
        rel = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        if offset is None:
            pos = jnp.where(rel < lengths[:, None], rel, 2 ** 30)
            end = lengths
        else:
            off = jnp.asarray(offset, jnp.int32)
            pos = jnp.where(rel < lengths[:, None], rel + off[:, None],
                            2 ** 30)
            end = off + lengths
        logits, caches, _ = self.forward(params, batch, caches=caches,
                                         positions=pos,
                                         last_index=lengths - 1)

        def fix(path, a):
            name = str(path[-1]) if path else ""
            if "index" in name and hasattr(a, "dtype") and a.ndim >= 1 \
                    and "pos" not in name:
                return jnp.broadcast_to(end, a.shape).astype(a.dtype)
            return a

        caches = jax.tree_util.tree_map_with_path(fix, caches)
        return logits, caches

    def decode_step(self, params, batch, caches):
        """One (or a few, for speculative verify) new tokens per sequence
        against existing caches."""
        return self.decode_step_with_load(params, batch, caches)[:2]

    def decode_step_with_load(self, params, batch, caches):
        """:meth:`decode_step` plus the step's held-expert loads: int32
        [n_moe_layers, 3] (pairs routed to held experts, most rows on
        one held expert, held experts with any row) over the rows that decode (a position below
        the empty sentinel)."""
        idx = self._cache_index(caches)          # [B] per-slot positions
        S = self._step_len(batch)
        positions = (idx[:, None] + jnp.arange(S)[None, :]).astype(jnp.int32)
        logits, caches, _, loads = self._forward(
            params, batch, caches=caches, positions=positions, decode=True)
        return logits, caches, loads

    def _step_len(self, batch) -> int:
        for k in ("inputs", "frame_embeddings"):
            if k in batch:
                return batch[k].shape[1]
        raise KeyError("cannot infer step length")

    def _batch_size(self, batch) -> int:
        for k in ("inputs", "frame_embeddings", "patch_embeddings"):
            if k in batch:
                return batch[k].shape[0]
        raise KeyError("cannot infer batch size")

    @staticmethod
    def _cache_index(caches):
        # index leaves are int32 [B] per layer, stacked [G, B]: pick any
        for g in caches.values():
            if isinstance(g, dict) and "index" in g:
                return g["index"][0]
        raise KeyError("no cache index found")

    # -- serving-side weight quantization ------------------------------------
    def quantize(self, params, plan=None, mesh=None, rules=None):
        """Rewrite ``params`` per a :class:`~repro.quant.plan.QuantPlan`
        (default: the full plan — every weight matmul on the fused INT8
        CIM pipeline).

        Covered layers become :class:`~repro.quant.linear.
        QuantizedLinear` leaves, which the layer applies
        (``attention_apply``, ``mlp_apply``, ``moe_apply``) detect and
        dispatch uniformly: attention q/k/v as one wide fused GEMM,
        out-projection and MLP down-projection with the block residual
        in their epilogues, MoE experts as ONE grouped pipeline over the
        stacked capacity buffers (dispatches constant in the expert
        count).  This is the serving engine's decode path in INT8 mode.

        ``mesh`` places the quantized tree for tensor-parallel serving:
        every leaf is device_put with the sharding its logical axes
        resolve to (``quant.plan.plan_axes`` — q and scale co-sharded
        on the output-channel axis, out-proj/down on the input axis,
        MoE stacks on the expert axis), so each device holds only its
        weight shard and the shard_map'd fused pipelines
        (``quant/tp.py``) consume it in place.
        """
        from repro.quant.plan import FULL_INT8, apply_plan, plan_axes
        plan = FULL_INT8 if plan is None else plan
        qparams = apply_plan(self.groups, params, plan)
        if mesh is not None:
            from repro.parallel.sharding import make_shardings
            axes = plan_axes(self.groups, self.param_axes(), plan)
            qparams = jax.device_put(
                qparams, make_shardings(mesh, qparams, axes, rules))
        return qparams

    # -- caches ---------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, kv_dtype=None):
        """``kv_dtype="int8"`` overrides ``cfg.kv_cache_dtype`` — the
        serving engine uses it to store KV int8 when the quant plan
        covers ``attn_kv`` (quantize fused into the cache-update site,
        flash-decode dequantizes in-kernel)."""
        caches = {}
        for gi, (spec, count) in enumerate(self.groups):
            one = block_cache_init(spec, self.cfg, batch, max_len,
                                   kv_dtype=kv_dtype)
            caches[f"group_{gi}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (count, *a.shape)).copy()
                if hasattr(a, "shape") else a, one)
        return caches

    def init_paged_cache(self, batch: int, num_blocks: int, block_size: int,
                         max_blocks: int, kv_dtype=None):
        """Paged (block-table) caches for the continuously-batched
        serving engine: every attention layer gets its own pool of
        ``num_blocks`` fixed-size blocks (block 0 reserved as the
        all-empty null block) plus per-row block tables of width
        ``max_blocks``; an MLA layer's pools hold its latent and rope
        key per token (``mla.init_paged_latent_cache``) under the same
        tables.  Recurrent mixers have no position-keyed cache to
        page."""
        kv = kv_dtype or self.cfg.kv_cache_dtype
        dt = jnp.int8 if kv == "int8" else jnp.bfloat16
        caches = {}
        for gi, (spec, count) in enumerate(self.groups):
            mixer = spec[0]
            if mixer == "mla":
                one = mla_mod.init_paged_latent_cache(
                    batch, num_blocks, block_size, max_blocks, self.cfg.mla,
                    dtype=dt)
            elif mixer in ("attn", "attn_local"):
                one = attn_mod.init_paged_kv_cache(
                    batch, num_blocks, block_size, max_blocks,
                    self.cfg.n_kv_heads, self.cfg.head_dim, dtype=dt)
            else:
                raise NotImplementedError(
                    f"paged KV cache: unsupported mixer {mixer!r} (only "
                    f"attention and MLA layers hold a position-keyed "
                    f"cache)")
            caches[f"group_{gi}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (count, *a.shape)).copy()
                if hasattr(a, "shape") else a, one)
        return caches

    def paged_cache_axes(self, kv_dtype=None):
        kv = kv_dtype or self.cfg.kv_cache_dtype
        axes = {}
        for gi, (spec, _) in enumerate(self.groups):
            one = (mla_mod.paged_latent_cache_logical_axes()
                   if spec[0] == "mla" else
                   attn_mod.paged_kv_cache_logical_axes(
                       quantized=kv == "int8"))
            axes[f"group_{gi}"] = jax.tree.map(
                lambda a: ("layers", *a) if isinstance(a, tuple) else a, one,
                is_leaf=lambda a: isinstance(a, tuple))
        return axes

    def abstract_cache(self, batch: int, max_len: int, kv_dtype=None):
        return jax.eval_shape(
            lambda: self.init_cache(batch, max_len, kv_dtype=kv_dtype))

    def cache_axes(self, kv_dtype=None):
        axes = {}
        for gi, (spec, _) in enumerate(self.groups):
            one = block_cache_axes(spec, self.cfg, kv_dtype=kv_dtype)
            axes[f"group_{gi}"] = jax.tree.map(
                lambda a: ("layers", *a) if isinstance(a, tuple) else a, one,
                is_leaf=lambda a: isinstance(a, tuple))
        return axes


@functools.lru_cache(maxsize=32)
def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
