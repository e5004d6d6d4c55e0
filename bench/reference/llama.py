"""Llama-architecture decoder (deepseek-67b, arXiv:2401.02954) in float32.

Pre-norm blocks: RMSNorm (eps 1e-6) -> GQA attention with rotary
embeddings (rotate-half layout, theta from the config) and a causal mask
-> residual; RMSNorm -> SwiGLU MLP (``silu(x W_gate) * (x W_up) W_down``)
-> residual; final RMSNorm and an untied LM head.

Weights come from the seed as the program's loader draws them: the model
key splits into five (embedding, head, -, layers, -), the layer key into
one key per layer, each layer key into four (attention, MLP, -, -), the
attention key into (q, k, v, o) and the MLP key into (up, down, gate).

The check runs layer by layer over every sampled sequence, so that only
one layer's weights live on the device at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512          # query rows per attention block (bounds scores)
SEQ_BUCKET = 512       # sequences pad to a multiple (bounds compiles)


def _tn(key, shape, scale):
    return (scale * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)).astype(jnp.bfloat16)


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kh": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim", d // h), "ff": cfg["intermediate_size"],
            "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
            "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"]}


def fake_quant(w, in_axes: tuple, bits: int):
    """Symmetric per-output-channel quantize and dequantize."""
    top = 2 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -top, top) * s


def fake_quant_rows(x, bits: int | None, axes=(-1,)):
    """Per-row dynamic activation quantization (absmax over ``axes``);
    the identity when ``bits`` is None."""
    return x if not bits else fake_quant(x, axes, bits)


def embed_head(cfg: dict, key):
    x = dims(cfg)
    keys = jax.random.split(key, 5)
    emb = jax.jit(lambda k: _tn(k, (x["v"], x["d"]), 1.0))(keys[0])
    head = jax.jit(lambda k: _tn(k, (x["d"], x["v"]),
                                 1.0 / math.sqrt(x["d"])))(keys[1])
    return emb, head


def layer_weights(cfg: dict, key, j: int) -> dict:
    """Layer ``j``'s weights (bf16 values), drawn from the model key."""
    x = dims(cfg)
    d, h, kh, hd, ff = x["d"], x["h"], x["kh"], x["hd"], x["ff"]

    @jax.jit
    def make(k):
        lk = jax.random.split(jax.random.split(k, 5)[3], x["layers"])[j]
        km, kf, _, _ = jax.random.split(lk, 4)
        kq, kk, kv, ko = jax.random.split(km, 4)
        k1, k2, k3 = jax.random.split(kf, 3)
        sd = 1.0 / math.sqrt(d)
        return {"q": _tn(kq, (d, h, hd), sd), "k": _tn(kk, (d, kh, hd), sd),
                "v": _tn(kv, (d, kh, hd), sd),
                "o": _tn(ko, (h * hd, d),
                         1.0 / math.sqrt(h * hd)).reshape(h, hd, d),
                "up": _tn(k1, (d, ff), sd),
                "down": _tn(k2, (ff, d), 1.0 / math.sqrt(ff)),
                "gate": _tn(k3, (d, ff), sd)}

    return make(key)


IN_AXES = {"q": (0,), "k": (0,), "v": (0,), "o": (0, 1), "up": (0,),
           "down": (0,), "gate": (0,)}


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs          # [S, hd/2]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, eps, theta, bits):
    """One block over ``x`` [S, d] f32 at positions 0..S-1.  With
    ``bits`` the weights, the inputs of every planned matmul and the
    cached K/V are quantized to that many bits, where the program
    quantizes to 8."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    if bits:
        w = {k: fake_quant(v, IN_AXES[k], bits) for k, v in w.items()}
    S = x.shape[0]
    pos = jnp.arange(S, dtype=jnp.int32)
    h = fake_quant_rows(_rms(x, eps), bits)
    q = _rope(jnp.einsum("sd,dhk->shk", h, w["q"]), pos, theta)
    k = fake_quant_rows(
        _rope(jnp.einsum("sd,dhk->shk", h, w["k"]), pos, theta), bits)
    v = fake_quant_rows(jnp.einsum("sd,dhk->shk", h, w["v"]), bits)
    H, hd = q.shape[1], q.shape[2]
    kh = k.shape[1]
    qg = q.reshape(S // Q_BLOCK, Q_BLOCK, kh, H // kh, hd)

    def block(args):
        qb, i = args
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    att = jax.lax.map(block, (qg, jnp.arange(S // Q_BLOCK)))
    att = fake_quant_rows(att.reshape(S, H, hd), bits, (-2, -1))
    x = x + jnp.einsum("shk,hkd->sd", att, w["o"])
    h = fake_quant_rows(_rms(x, eps), bits)
    mlp = fake_quant_rows(jax.nn.silu(h @ w["gate"]) * (h @ w["up"]), bits)
    return x + mlp @ w["down"]


def _logits(x, rows, head, eps):
    return _rms(x[rows], eps) @ head.astype(jnp.float32)


def served_gaps(cfg: dict, key, seqs, bits_control: int | None = None
                ) -> list[dict]:
    """For each ``(tokens, n_prompt)``: the widest gap by which a served
    token's reference logit lies below the reference's best, over the
    served tokens ``tokens[n_prompt:]``.

    With ``bits_control`` the same forward also runs at that precision
    (weights, matmul inputs and K/V), and ``control_gap`` is the widest
    gap of the token that the lower-precision model puts first, at the
    same positions.
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
        for j in range(x["layers"]):
            w = layer_weights(cfg, key, j)
            states = [layer(s, w, x["eps"], x["theta"], 0) for s in states]
            if bits_control:
                ctl = [layer(s, w, x["eps"], x["theta"], bits_control)
                       for s in ctl]
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
