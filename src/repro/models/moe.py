"""Mixture-of-Experts FFN: shared + routed top-k experts, one chip's share.

Routing is over every routed expert, as published:

* ``scoring="softmax"`` (qwen2-moe): softmax over the router logits,
  top-k, optionally renormalised.
* ``scoring="sigmoid_group"`` (DeepSeek-V3's ``noaux_tc``): sigmoid
  scores; the selection adds ``e_score_correction_bias`` (``router_bias``)
  to them, keeps the ``topk_group`` of ``n_group`` expert groups whose
  two best biased scores sum highest, and takes the top-k experts inside
  them; the gates are the unbiased scores of those experts, renormalised
  (``norm_topk_prob``) and multiplied by ``routed_scaling_factor``.

A layer holds the routed experts of one expert-parallel shard:
``n_expert_shards`` shards of ``n_routed_experts / n_expert_shards``
experts each, this layer's being shard ``expert_shard`` (experts
``expert_shard * n_held`` onward).  Expert ``e``'s weights are drawn
from ``fold_in(key, e)``, so a shard draws the same experts as the
uncut model, and the shards' outputs add up to the uncut layer's (with
the shared expert counted once).  The router keeps its full width.

Serving is dropless: each token's pairs with held experts are sorted
by expert into row tiles (each expert's rows padded to whole tiles, so
the work and the buffer follow the routed pairs, not E_held x tokens),
run through the grouped experts (the fused int8 ``cim_grouped_*``
kernels under a ``QuantPlan``, tile by tile against the tile's expert),
and gathered back per (token, k) in a fixed order, so a row's output
does not depend on the rest of its batch.
Training (``Model.loss``) keeps the GShard capacity path, per-row
capacity buffers that drop overflow, because its buffers scale with the
sequence; it is also where the Switch-style load-balance auxiliary is
used.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .layers import Param, mlp_init, mlp_apply, truncated_normal_init


@dataclass(frozen=True)
class MoEConfig:
    n_routed_experts: int
    top_k: int
    d_expert: int                  # per-expert FFN hidden size
    n_shared_experts: int = 0
    shared_d_ff: int = 0           # hidden size of the shared expert MLP
    capacity_factor: float = 1.25  # training's capacity path only
    norm_topk_prob: bool = True
    aux_loss_coef: float = 0.001
    first_k_dense: int = 0         # leading dense layers (deepseek-v3: 3)
    scoring: str = "softmax"       # "softmax" | "sigmoid_group"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    n_expert_shards: int = 1       # expert parallelism: shards of experts
    expert_shard: int = 0          # the shard this layer holds

    @property
    def n_held(self) -> int:
        return self.n_routed_experts // self.n_expert_shards

    @property
    def held_offset(self) -> int:
        return self.expert_shard * self.n_held


def moe_init(key, d_model: int, cfg: MoEConfig, activation: str = "swiglu",
             dtype=jnp.bfloat16) -> dict:
    if cfg.n_routed_experts % cfg.n_expert_shards:
        raise ValueError("n_expert_shards must divide n_routed_experts")
    kr, ku, kg, kd, ks = jax.random.split(key, 5)
    E, F = cfg.n_routed_experts, cfg.d_expert
    gated = activation in ("geglu", "swiglu")
    scale = 1.0 / (d_model ** 0.5)
    ids = cfg.held_offset + jnp.arange(cfg.n_held)

    def experts(k, shape, s):
        # one key per expert id: any shard draws the uncut model's experts
        return jax.vmap(lambda e: truncated_normal_init(
            jax.random.fold_in(k, e), shape, dtype, s))(ids)

    kr, kb = jax.random.split(kr)
    p = {
        "router": Param(
            truncated_normal_init(kr, (d_model, E), jnp.float32, scale),
            ("fsdp", None)),
        "up": Param(experts(ku, (d_model, F), scale),
                    ("expert", "fsdp", "mlp")),
        "down": Param(experts(kd, (F, d_model), 1.0 / F ** 0.5),
                      ("expert", "mlp", "fsdp")),
    }
    if cfg.scoring == "sigmoid_group":
        # e_score_correction_bias: learned in the published model, drawn
        # here (a 0.05 spread moves selections the way a trained one does)
        p["router_bias"] = Param(
            truncated_normal_init(kb, (E,), jnp.float32, 0.05), (None,))
    if gated:
        p["gate"] = Param(experts(kg, (d_model, F), scale),
                          ("expert", "fsdp", "mlp"))
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks, d_model,
                               cfg.shared_d_ff or cfg.d_expert *
                               cfg.n_shared_experts, activation, dtype)
    return p


def _activate(name: str, x):
    if name in ("gelu", "geglu"):
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def moe_route(params: dict, x: jax.Array, cfg: MoEConfig):
    """The published router over all ``n_routed_experts``.

    x [..., d] -> (gates [..., K] f32, expert ids [..., K] int32, the
    per-expert routing mass [..., E] the auxiliary loss reads)."""
    E, K = cfg.n_routed_experts, cfg.top_k
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        params["router"])
    if cfg.scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, K)
        if cfg.norm_topk_prob:
            gates = gates / jnp.sum(gates, -1, keepdims=True)
        return gates, ids, probs
    if cfg.scoring != "sigmoid_group":
        raise ValueError(f"unknown MoE scoring {cfg.scoring!r}")
    scores = jax.nn.sigmoid(logits)
    choice = scores + params["router_bias"]
    G = cfg.n_group
    grouped = choice.reshape(*choice.shape[:-1], G, E // G)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # [..., G]
    _, top_groups = jax.lax.top_k(group_score, cfg.topk_group)
    group_mask = jnp.sum(jax.nn.one_hot(top_groups, G, dtype=jnp.int32),
                         axis=-2) > 0
    expert_mask = jnp.repeat(group_mask, E // G, axis=-1)
    _, ids = jax.lax.top_k(jnp.where(expert_mask, choice, 0.0), K)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.norm_topk_prob:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    gates = gates * cfg.routed_scaling_factor
    return gates, ids, scores / jnp.sum(scores, -1, keepdims=True)


def held_load(ids: jax.Array, cfg: MoEConfig,
              valid: Optional[jax.Array] = None) -> jax.Array:
    """int32 [3]: the (row, expert) pairs routed to held experts, the
    most rows on one held expert, and the held experts with any row,
    over the rows ``valid`` keeps (ids [..., K]; valid [...] bool,
    default all)."""
    local = ids.reshape(-1, ids.shape[-1]) - cfg.held_offset
    held = (local >= 0) & (local < cfg.n_held)
    if valid is not None:
        held &= valid.reshape(-1, 1)
    counts = jnp.zeros((cfg.n_held,), jnp.int32).at[
        jnp.where(held, local, cfg.n_held)].add(1, mode="drop")
    return jnp.stack([jnp.sum(counts), jnp.max(counts),
                      jnp.sum(counts > 0).astype(jnp.int32)])


def _quantized(params: dict) -> bool:
    from repro.quant.linear import QuantizedLinear  # local: no cycle
    return isinstance(params.get("up"), QuantizedLinear)


def _run_experts(params: dict, xe: jax.Array, counts: jax.Array,
                 activation: str) -> jax.Array:
    """xe [E_held, T, d] per-expert buffers -> [E_held, T, d]."""
    from repro.quant.linear import quantized_moe_apply  # local: no cycle
    if _quantized(params):
        # QuantPlan moe_experts path: every held expert's buffer runs the
        # fused INT8 pipeline in a constant number of Pallas dispatches
        # (one quantize + one grouped gated GEMM + one grouped down GEMM),
        # with the expert index as a kernel grid dimension; ``counts``
        # is the zero-row skip list (empty experts run no MXU work), and
        # under a model-axis sharding context the grouped pipeline shards
        # over the expert axis (quant/tp.py).
        return quantized_moe_apply(params, xe, activation, use_kernel=None,
                                   expert_counts=counts)
    from repro.parallel.context import shard
    up = jnp.einsum("etd,edf->etf", xe, params["up"])
    if "gate" in params:
        h = _activate(activation, jnp.einsum("etd,edf->etf", xe,
                                             params["gate"])) * up
    else:
        h = _activate(activation, up)
    h = shard(h, ("expert", None, "mlp"))
    return jnp.einsum("etf,efd->etd", h, params["down"])


def _run_ragged(params: dict, xt: jax.Array, groups: jax.Array,
                live: jax.Array, sizes: jax.Array,
                activation: str) -> jax.Array:
    """xt [n_tiles, tm, d] row tiles sorted by expert, tile t holding
    rows of expert ``groups[t]`` (``live[t]`` 0: an empty tile; ``sizes``
    [E_held] the rows each expert's tiles span) -> [n_tiles, tm, d]."""
    from repro.quant.linear import quantized_moe_apply  # local: no cycle
    if _quantized(params):
        # the grouped INT8 pipeline, tile t against expert groups[t]'s
        # stacks; empty tiles run no MXU work and fetch no weights
        return quantized_moe_apply(params, xt, activation, use_kernel=None,
                                   expert_counts=live, groups=groups)
    n_tiles, tm, d = xt.shape
    x = xt.reshape(n_tiles * tm, d)
    up = jax.lax.ragged_dot(x, params["up"], sizes)
    if "gate" in params:
        h = _activate(activation,
                      jax.lax.ragged_dot(x, params["gate"], sizes)) * up
    else:
        h = _activate(activation, up)
    return jax.lax.ragged_dot(h, params["down"], sizes).reshape(xt.shape)


def _row_tile(T: int) -> int:
    """Rows of a ragged tile: the token count rounded up to the int8
    sublane multiple, at most 256 (the grouped kernels' row block)."""
    return min(256, -(-T // 32) * 32)


def _ragged_dispatch(params: dict, x2: jax.Array, flat_e: jax.Array,
                     K: int, Eh: int, activation: str) -> jax.Array:
    """Run every (token, k) pair with a held expert (``flat_e`` < Eh; the
    rest Eh) and return each pair's expert output [T*K, d] (zeros for
    the rest).

    Pairs are sorted by expert into row tiles of ``tm`` rows, each
    expert's rows padded to whole tiles, so a tile holds one expert's
    rows: the buffer holds T*min(K, Eh) rows plus a tile of padding per
    expert (and never more than Eh tiles per tm tokens), and the expert
    work is that of the routed pairs, not of E_held x T.  At T <= 256 it
    is one tile per expert with any row."""
    T, d = x2.shape
    n = T * K
    tm = _row_tile(T)
    n_tiles = min(-(-T * min(K, Eh) // tm) + Eh, Eh * -(-T // tm))
    R = n_tiles * tm
    counts = jnp.zeros((Eh,), jnp.int32).at[flat_e].add(1, mode="drop")
    sizes = -(-counts // tm) * tm                    # rows, whole tiles
    ends = jnp.cumsum(sizes)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    rank = jnp.arange(n) - jnp.searchsorted(se, se, side="left")
    start = (ends - sizes)[jnp.minimum(se, Eh - 1)]
    row_sorted = jnp.where(se < Eh, start + rank, R)
    row = jnp.zeros((n,), jnp.int32).at[order].set(row_sorted)
    src = jnp.full((R,), T, jnp.int32).at[row].set(
        jnp.arange(n, dtype=jnp.int32) // K, mode="drop")
    xr = x2.at[src].get(mode="fill", fill_value=0)            # [R, d]
    first = jnp.arange(n_tiles, dtype=jnp.int32) * tm
    # the expert each tile belongs to; tiles past the last one take the
    # last expert (they are empty and fetch no weights)
    groups = jnp.minimum(jnp.searchsorted(ends, first, side="right"),
                         Eh - 1).astype(jnp.int32)
    live = (first < ends[-1]).astype(jnp.int32)
    with jax.named_scope("moe.experts"):
        ye = _run_ragged(params, xr.reshape(n_tiles, tm, d), groups, live,
                         sizes, activation)
    return ye.reshape(R, d).at[row].get(mode="fill", fill_value=0)


def _dense_dispatch(params: dict, x2: jax.Array, flat_e: jax.Array,
                    K: int, Eh: int, activation: str) -> jax.Array:
    """Expert-parallel form of :func:`_ragged_dispatch` for a model-axis
    mesh that shards the held experts: per-expert buffers [E_held, T, d]
    with room for every token, which the grouped pipeline splits over
    the devices (quant/tp.py)."""
    T, d = x2.shape
    n = T * K
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    first = jnp.searchsorted(se, se, side="left")
    slot = jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n) - first)
    tok = jnp.arange(n) // K
    xe = jnp.zeros((Eh, T, d), x2.dtype).at[flat_e, slot].set(
        x2[tok], mode="drop")
    counts = jnp.zeros((Eh,), jnp.int32).at[flat_e].add(1, mode="drop")
    with jax.named_scope("moe.experts"):
        ye = _run_experts(params, xe, counts, activation)
    return ye.at[flat_e, slot].get(mode="fill", fill_value=0)


def moe_experts(params: dict, x: jax.Array, gates: jax.Array,
                ids: jax.Array, cfg: MoEConfig,
                activation: str = "swiglu") -> jax.Array:
    """Dropless held-expert FFN plus the shared expert.

    x [B, S, d]; gates / ids [B, S, K] from :func:`moe_route`.  Every
    (token, k) pair whose expert is held runs through its expert
    (:func:`_ragged_dispatch`; nothing overflows); pairs with absent
    experts contribute nothing.  Outputs are gathered back per pair and
    summed over k in a fixed order, so a token's output depends only on
    that token.
    """
    from repro.quant.linear import _tp_mesh_for  # local: no cycle
    B, S, d = x.shape
    K, Eh = cfg.top_k, cfg.n_held
    T, n = B * S, B * S * K
    local = ids.reshape(n) - cfg.held_offset
    held = (local >= 0) & (local < Eh)
    flat_e = jnp.where(held, local, Eh)
    dispatch = (_dense_dispatch
                if _quantized(params) and _tp_mesh_for(Eh) is not None
                else _ragged_dispatch)
    y = dispatch(params, x.reshape(T, d), flat_e, K, Eh, activation)
    w = jnp.where(held, gates.reshape(n), 0.0).astype(jnp.float32)
    out = jnp.sum((y.astype(jnp.float32) * w[:, None]).reshape(T, K, d),
                  axis=1)
    out = out.reshape(B, S, d)
    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            out = out + mlp_apply(params["shared"], x, activation).astype(
                jnp.float32)
    return out.astype(x.dtype)


def _aux_loss(probs, ids, cfg: MoEConfig):
    """Switch-style load balance: routing mass x token fraction."""
    E = cfg.n_routed_experts
    lead = tuple(range(probs.ndim - 1))
    me = jnp.mean(probs, axis=lead)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32),
                          axis=-2), axis=lead)
    return cfg.aux_loss_coef * E * jnp.sum(me * ce)


def moe_layer(params: dict, x: jax.Array, cfg: MoEConfig,
              activation: str = "swiglu", capacity: Optional[int] = None,
              train: bool = False, valid: Optional[jax.Array] = None):
    """x: [B, S, d] -> (out [B, S, d], aux_loss scalar, held-expert load
    int32 [3] over the rows ``valid`` [B, S] keeps (:func:`held_load`)).

    The dropless held-expert path (:func:`moe_experts`) unless ``train``
    or ``capacity`` asks for the training capacity path."""
    with jax.named_scope("moe.router"):
        gates, ids, probs = moe_route(params, x, cfg)
    aux = _aux_loss(probs, ids, cfg)
    load = held_load(ids, cfg, valid)
    if not train and capacity is None:
        return moe_experts(params, x, gates, ids, cfg, activation), aux, load
    return _capacity_apply(params, x, gates, ids, cfg, activation,
                           capacity), aux, load


def moe_apply(params: dict, x: jax.Array, cfg: MoEConfig,
              activation: str = "swiglu", capacity: Optional[int] = None,
              train: bool = False) -> tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (out [B, S, d], aux_loss scalar); see
    :func:`moe_layer`."""
    return moe_layer(params, x, cfg, activation, capacity, train)[:2]


def _capacity_apply(params, x, gate_vals, expert_ids, cfg: MoEConfig,
                    activation, capacity):
    """Training's GShard path: each batch row is a dispatch group with
    its own capacity (C = S*K/E_held * factor), so the capacity buffers
    are [B, E, C, d] -- shardable over batch x expert -- and overflow
    drops tokens, which the auxiliary loss works against."""
    from repro.parallel.context import shard

    B, S, d = x.shape
    K, Eh = cfg.top_k, cfg.n_held
    if capacity is None:
        capacity = int(S * K / Eh * cfg.capacity_factor) + 1
    n = S * K
    local = expert_ids.reshape(B, n) - cfg.held_offset
    held = (local >= 0) & (local < Eh)
    flat_e = jnp.where(held, local, Eh)
    flat_g = jnp.where(held, gate_vals.reshape(B, n), 0.0)
    tok_of = jnp.broadcast_to(jnp.arange(n) // K, (B, n))

    order = jnp.argsort(flat_e, axis=1)
    se = jnp.take_along_axis(flat_e, order, axis=1)           # [B, n]
    st = jnp.take_along_axis(tok_of, order, axis=1)
    sg = jnp.take_along_axis(flat_g, order, axis=1)
    first = jax.vmap(lambda a: jnp.searchsorted(a, a, side="left"))(se)
    pos = jnp.arange(n)[None, :] - first
    keep = (pos < capacity) & (se < Eh)
    pos_c = jnp.where(keep, pos, 0)
    se_c = jnp.where(keep, se, 0)

    xe = jnp.zeros((B, Eh, capacity, d), x.dtype)
    upd = jnp.where(keep[..., None],
                    jnp.take_along_axis(x, st[..., None], axis=1), 0)
    xe = jax.vmap(lambda buf, e, p, u: buf.at[e, p].add(u, mode="drop"))(
        xe, se_c, pos_c, upd.astype(x.dtype))
    xe = shard(xe, ("batch", "expert", None, None))
    counts = jnp.zeros((Eh,), jnp.int32).at[se.reshape(-1)].add(
        1, mode="drop")
    xg = xe.transpose(1, 0, 2, 3).reshape(Eh, B * capacity, d)
    ye = _run_experts(params, xg, counts, activation)
    ye = ye.reshape(Eh, B, capacity, d).transpose(1, 0, 2, 3)
    ye = shard(ye, ("batch", "expert", None, None))

    back = jax.vmap(lambda buf, e, p: buf[e, p])(ye, se_c, pos_c)
    back = jnp.where(keep[..., None], back, 0) * sg[..., None].astype(ye.dtype)
    out = jax.vmap(lambda o, t, u: o.at[t].add(u, mode="drop"))(
        jnp.zeros((B, S, d), ye.dtype), st, back)

    if cfg.n_shared_experts:
        out = out + mlp_apply(params["shared"], x, activation)
    return out.astype(x.dtype)
