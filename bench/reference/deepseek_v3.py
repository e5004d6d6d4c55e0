"""DeepSeek-V3 decoder (arXiv:2412.19437) in float32, one chip's share
of the routed experts.

Pre-norm blocks (RMSNorm, eps from the config): multi-head latent
attention, non-absorbed -- ``c_q = RMSNorm(h W_qa)``, ``q = c_q W_qb``
split into 128 no-rope and 64 rope dims per head; ``[c_kv | k_r] = h
W_kva``, ``c_kv`` RMS-normed, ``k_r`` one rope key shared by the heads;
``[k_nope | v] = c_kv W_kvb`` per head; YaRN rope on the rope dims
(``rope_scaling``: frequencies blended by the beta_fast / beta_slow ramp,
softmax scale ``1/sqrt(192) * mscale(factor, mscale_all_dim)**2``),
causal softmax; ``o = attn W_o``.  Then a SwiGLU FFN in the first
``first_k_dense_replace`` layers; after them the MoE: the ``noaux_tc``
router over all ``n_routed_experts`` (sigmoid scores; selection on
score + ``e_score_correction_bias``, the ``topk_group`` groups of
``n_group`` whose two best biased scores sum highest, top-k inside them;
gates the unbiased scores, normalised, times ``routed_scaling_factor``),
the part of the held experts (``expert_shard`` of ``n_expert_shards``),
nothing for the absent ones, plus the shared expert.  Final RMSNorm and
an untied head.  Rope uses the rotate-half layout, as the program does.
The multi-token-prediction layer is not part of the forward.

Weights come from the seed as the program's loader draws them: the model
key splits into (number of layer groups + 4): embedding, head, -, then
one key per group (the dense layers, then the MoE layers), each split
into one key per layer; a layer key splits into four (attention, FFN,
-, -); the attention key into six (q_a, q_b, kv_a, kv_b, o, -); a dense
FFN key into (up, down, gate); an MoE key into five (router, up, gate,
down, shared), the router key into (router, bias), and routed expert
``e``'s weights come from ``fold_in(<up|gate|down key>, e)``.

The check runs layer by layer over every sampled sequence, so that only
one layer's weights live on the device at a time.  ``bits`` quantizes
where the program runs int8: every plan-covered weight (MLA q_a, kv_a,
q_b, o; FFN and experts) per output channel, those matmuls' input rows,
and the latent and rope key the cache holds per token.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.llama import fake_quant, fake_quant_rows

Q_BLOCK = 128          # query rows per attention block (bounds scores)
HEAD_GROUP = 32        # heads whose K/V are up-projected at once
SEQ_BUCKET = 2048      # sequences pad to a multiple (bounds compiles)


def _tn(key, shape, scale, dtype=jnp.bfloat16):
    return (scale * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)


def dims(cfg: dict) -> dict:
    rs = cfg["rope_scaling"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "q_lora": cfg["q_lora_rank"], "r": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "ff": cfg["intermediate_size"],
            "F": cfg["moe_intermediate_size"], "E": cfg["n_routed_experts"],
            "K": cfg["num_experts_per_tok"], "groups": cfg["n_group"],
            "topk_groups": cfg["topk_group"],
            "scaling": cfg["routed_scaling_factor"],
            "norm_topk": cfg["norm_topk_prob"],
            "shared": cfg["n_shared_experts"],
            "held": cfg["n_routed_experts_held"],
            "shard": cfg["expert_shard"],
            "layers": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"],
            "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
            "theta": cfg["rope_theta"], "factor": rs["factor"],
            "orig": rs["original_max_position_embeddings"],
            "beta_fast": rs["beta_fast"], "beta_slow": rs["beta_slow"],
            "mscale": rs["mscale"], "mscale_all_dim": rs["mscale_all_dim"]}


def _groups(x: dict) -> list:
    """(kind, first layer, count) of each scan group, in order."""
    out = []
    if x["dense"]:
        out.append(("dense", 0, min(x["dense"], x["layers"])))
    if x["layers"] > x["dense"]:
        out.append(("moe", x["dense"], x["layers"] - x["dense"]))
    return out


def _mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1.0 else 0.1 * m * math.log(scale) + 1.0


def yarn(x: dict):
    """(inverse frequencies [rope/2], sin/cos factor, softmax scale)."""
    dim, base = x["rope"], x["theta"]

    def corr(rot):
        return (dim * math.log(x["orig"] / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(x["beta_fast"])), 0)
    high = min(math.ceil(corr(x["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = extra / x["factor"] * ramp + extra * (1.0 - ramp)
    cos_scale = (_mscale(x["factor"], x["mscale"])
                 / _mscale(x["factor"], x["mscale_all_dim"]))
    sm = ((x["nope"] + x["rope"]) ** -0.5
          * _mscale(x["factor"], x["mscale_all_dim"]) ** 2)
    return jnp.asarray(inv, jnp.float32), cos_scale, sm


def embed_head(cfg: dict, key):
    x = dims(cfg)
    keys = jax.random.split(key, len(_groups(x)) + 4)
    emb = jax.jit(lambda k: _tn(k, (x["vocab"], x["d"]), 1.0))(keys[0])
    head = jax.jit(lambda k: _tn(k, (x["d"], x["vocab"]),
                                 1.0 / math.sqrt(x["d"])))(keys[1])
    return emb, head


def layer_weights(cfg: dict, key, j: int) -> dict:
    """Layer ``j``'s weights (bf16 values; router and bias f32)."""
    x = dims(cfg)
    groups = _groups(x)
    gi = next(i for i, (_, lo, n) in enumerate(groups) if lo <= j < lo + n)
    kind, lo, count = groups[gi]
    d, h, r, rope = x["d"], x["h"], x["r"], x["rope"]
    qk = x["nope"] + rope

    @jax.jit
    def make(k):
        keys = jax.random.split(k, len(groups) + 4)
        lk = jax.random.split(keys[3 + gi], count)[j - lo]
        km, kf, _, _ = jax.random.split(lk, 4)
        ks = jax.random.split(km, 6)
        # the program's scales, expression for expression: 1/sqrt(fan_in)
        # for its linear layers, 1/fan_in**0.5 for the router and experts
        def lin(kk, shape, fan_in):
            return _tn(kk, shape, 1.0 / math.sqrt(fan_in))

        w = {"q_down": lin(ks[0], (d, x["q_lora"]), d),
             "q_up": lin(ks[1], (x["q_lora"], h, qk), x["q_lora"]),
             "kv_down": lin(ks[2], (d, r + rope), d),
             "kv_up": lin(ks[3], (r, h, x["nope"] + x["v"]), r),
             "o": lin(ks[4], (h * x["v"], d), h * x["v"]).reshape(
                 h, x["v"], d)}
        if kind == "dense":
            k1, k2, k3 = jax.random.split(kf, 3)
            w.update(up=lin(k1, (d, x["ff"]), d),
                     down=lin(k2, (x["ff"], d), x["ff"]),
                     gate=lin(k3, (d, x["ff"]), d))
            return w
        F = x["F"]
        kr, ku, kg, kd, ksh = jax.random.split(kf, 5)
        kr, kb = jax.random.split(kr)
        ids = x["shard"] * x["held"] + jnp.arange(x["held"])

        def experts(kk, shape, s):
            return jax.vmap(lambda e: _tn(jax.random.fold_in(kk, e), shape,
                                          s))(ids)

        k1, k2, k3 = jax.random.split(ksh, 3)
        Fs = F * x["shared"]
        sd = 1.0 / (d ** 0.5)
        w.update(router=_tn(kr, (d, x["E"]), sd, jnp.float32),
                 bias=_tn(kb, (x["E"],), 0.05, jnp.float32),
                 up=experts(ku, (d, F), sd),
                 gate=experts(kg, (d, F), sd),
                 down=experts(kd, (F, d), 1.0 / F ** 0.5),
                 s_up=lin(k1, (d, Fs), d),
                 s_down=lin(k2, (Fs, d), Fs),
                 s_gate=lin(k3, (d, Fs), d))
        return w

    return make(key)


# input-channel axes of each planned weight (per-output-channel quant)
IN_AXES = {"q_down": (0,), "kv_down": (0,), "q_up": (0,), "o": (0, 1),
           "up": (-2,), "gate": (-2,), "down": (-2,), "s_up": (0,),
           "s_gate": (0,), "s_down": (0,)}


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, inv, cos_scale):
    """Rotate-half rope on [S, ..., rope] at positions ``pos`` [S]."""
    ang = pos.astype(jnp.float32)[:, None] * inv               # [S, rope/2]
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), ang.shape[-1])
    sin, cos = cos_scale * jnp.sin(ang), cos_scale * jnp.cos(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def route(h, router, bias, x: dict):
    """The ``noaux_tc`` router: (gates [S, K], expert ids [S, K])."""
    E, G = x["E"], x["groups"]
    scores = jax.nn.sigmoid(h @ router)
    choice = scores + bias
    grouped = choice.reshape(-1, G, E // G)
    gscore = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, top = jax.lax.top_k(gscore, x["topk_groups"])
    gmask = jnp.zeros_like(gscore, bool).at[
        jnp.arange(gscore.shape[0])[:, None], top].set(True)
    masked = jnp.where(jnp.repeat(gmask, E // G, axis=-1), choice, 0.0)
    _, ids = jax.lax.top_k(masked, x["K"])
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    if x["norm_topk"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return gates * x["scaling"], ids


def _swiglu(h, up, gate, down, bits):
    mid = fake_quant_rows(jax.nn.silu(h @ gate) * (h @ up), bits)
    return mid @ down


def _layer(xs, w, xkey: tuple, kind: str, bits):
    """One block over ``xs`` [S, d] f32 at positions 0..S-1; ``xkey``
    is :func:`dims` as sorted items (a static argument)."""
    x = dict(xkey)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    if bits:
        w = {k: fake_quant(v, IN_AXES[k], bits) if k in IN_AXES else v
             for k, v in w.items()}
    eps = x["eps"]
    inv, cos_scale, sm = yarn(x)
    S = xs.shape[0]
    pos = jnp.arange(S, dtype=jnp.int32)
    nope, r = x["nope"], x["r"]

    h = fake_quant_rows(_rms(xs, eps), bits)
    cq = fake_quant_rows(_rms(h @ w["q_down"], eps), bits)
    q = jnp.einsum("sr,rhk->shk", cq, w["q_up"])
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, inv, cos_scale)
    ckv = h @ w["kv_down"]
    c_kv = fake_quant_rows(_rms(ckv[:, :r], eps), bits)
    k_r = fake_quant_rows(_rope(ckv[:, r:], pos, inv, cos_scale), bits)
    H = q.shape[1]
    hg = min(HEAD_GROUP, H)
    nq = S // Q_BLOCK

    def heads(args):
        """One group of heads: up-project its K/V, attend by q blocks."""
        qn, qr, kv_up = args                   # [S, hg, *], [r, hg, *]
        k_nope = jnp.einsum("sr,rhk->shk", c_kv, kv_up[..., :nope])
        v = jnp.einsum("sr,rhk->shk", c_kv, kv_up[..., nope:])

        def block(b):
            qnb, qrb, i = b
            s = (jnp.einsum("qhk,thk->hqt", qnb, k_nope)
                 + jnp.einsum("qhk,tk->hqt", qrb, k_r)) * sm
            qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s,
                          -jnp.inf)
            return jnp.einsum("hqt,thv->qhv", jax.nn.softmax(s, axis=-1), v)

        return jax.lax.map(block, (qn.reshape(nq, Q_BLOCK, *qn.shape[1:]),
                                   qr.reshape(nq, Q_BLOCK, *qr.shape[1:]),
                                   jnp.arange(nq)))

    def by_group(a, axis):
        a = jnp.moveaxis(a, axis, 0)
        return a.reshape(H // hg, hg, *a.shape[1:])

    att = jax.lax.map(heads, (
        jnp.moveaxis(by_group(q_nope, 1), 1, 2),
        jnp.moveaxis(by_group(q_rope, 1), 1, 2),
        jnp.moveaxis(by_group(w["kv_up"], 1), 1, 2)))
    # [groups, nq, Q_BLOCK, hg, v] -> [S, H, v]
    att = att.transpose(1, 2, 0, 3, 4).reshape(S, H, -1)
    att = fake_quant_rows(att, bits, (-2, -1))
    xs = xs + jnp.einsum("shv,hvd->sd", att, w["o"])

    hn = _rms(xs, eps)
    if kind == "dense":
        return xs + _swiglu(fake_quant_rows(hn, bits), w["up"], w["gate"],
                            w["down"], bits)
    gates, ids = route(hn, w["router"], w["bias"], x)
    hq = fake_quant_rows(hn, bits)
    out = _swiglu(hq, w["s_up"], w["s_gate"], w["s_down"], bits)
    base = x["shard"] * x["held"]
    for e in range(x["held"]):
        weight = jnp.sum(jnp.where(ids == base + e, gates, 0.0), axis=-1)
        out = out + weight[:, None] * _swiglu(hq, w["up"][e], w["gate"][e],
                                              w["down"][e], bits)
    return xs + out


def _logits(xs, rows, head, eps):
    return _rms(xs[rows], eps) @ head.astype(jnp.float32)


def served_gaps(cfg: dict, key, seqs, bits_control: int | None = None
                ) -> list[dict]:
    """For each ``(tokens, n_prompt)``: the widest gap by which a served
    token's reference logit lies below the reference's best, over the
    served tokens ``tokens[n_prompt:]``.

    With ``bits_control`` the same forward also runs at that precision,
    and ``control_gap`` is the widest gap of the token that the
    lower-precision model puts first, at the same positions.
    """
    x = dims(cfg)
    with jax.default_matmul_precision("highest"):
        emb, head = embed_head(cfg, key)
        layer = jax.jit(_layer, static_argnums=(2, 3, 4))
        states, ctl = [], []
        for toks, _ in seqs:
            S = -(-len(toks) // SEQ_BUCKET) * SEQ_BUCKET
            padded = np.zeros(S, np.int32)
            padded[:len(toks)] = toks
            h0 = jnp.take(emb, jnp.asarray(padded), axis=0).astype(
                jnp.float32)
            states.append(h0)
            ctl.append(h0)
        del emb
        xkey = tuple(sorted(x.items()))
        for j in range(x["layers"]):
            kind = "dense" if j < x["dense"] else "moe"
            w = layer_weights(cfg, key, j)
            states = [layer(s, w, xkey, kind, 0) for s in states]
            if bits_control:
                ctl = [layer(s, w, xkey, kind, bits_control) for s in ctl]
            del w
        logit_fn = jax.jit(_logits, static_argnums=(3,))
        out = []
        for i, (toks, n_prompt) in enumerate(seqs):
            rows = jnp.arange(n_prompt - 1, len(toks) - 1)
            served = jnp.asarray(toks[n_prompt:])
            ref = logit_fn(states[i], rows, head, x["eps"])
            best = ref.max(-1)
            gap = best - jnp.take_along_axis(ref, served[:, None], 1)[:, 0]
            res = {"tokens": int(len(served)),
                   "gap": float(gap.max()),
                   "top1": float(jnp.mean(ref.argmax(-1) == served))}
            if bits_control:
                c = logit_fn(ctl[i], rows, head, x["eps"]).argmax(-1)
                cgap = best - jnp.take_along_axis(ref, c[:, None], 1)[:, 0]
                res["control_gap"] = float(cgap.max())
            out.append(res)
    return out

