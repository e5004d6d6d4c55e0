"""DiT with adaLN conditioning (arXiv:2212.09748) and its DDIM sampler,
in float32.

Forward: patchify the latent into ``(H/p)*(W/p)`` tokens of ``p*p*C``
values (patch rows, patch columns, channels), linear patch embedding;
conditioning ``c = MLP(sinusoidal(t)) + table[y]``; each block
``x += g1 * attn(mod(LN(x), b1, s1))``, ``x += g2 * mlp(mod(LN(x), b2,
s2))`` with the six modulations from ``SiLU(c) W + b``, parameter-free
LayerNorm (eps 1e-6), full bidirectional attention and a GELU (tanh)
MLP; final adaLN (shift, scale), linear, unpatchify.  The learned-sigma
half of the output is dropped.

Sampler: DDIM (eta 0) over the linear-beta schedule (1e-4..0.02, 1000
steps) at ``num_steps`` evenly spaced timesteps, with classifier-free
guidance ``eps_u + s (eps_c - eps_u)`` against the null class.

Weights come from the seed as the program's loader draws them: the
model key splits into seven (patch embed, t-MLP in, t-MLP out, label
table, final adaLN, final linear, blocks), the block key into one key
per block, each block key into (attention, MLP, adaLN); biases start at
zero and the label table at scale 0.02.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.llama import fake_quant, fake_quant_rows


def _tn(key, shape, scale):
    return (scale * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)).astype(jnp.bfloat16)


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    p, c = cfg["patch_size"], cfg["in_channels"]
    return {"d": d, "h": cfg["num_heads"], "hd": d // cfg["num_heads"],
            "ff": int(cfg["mlp_ratio"] * d), "layers": cfg["depth"],
            "p": p, "c": c, "size": cfg["input_size"],
            "freq": cfg["frequency_embedding_size"],
            "classes": cfg["num_classes"],
            "out": p * p * c * (2 if cfg["learn_sigma"] else 1)}


# weights the int8 plan covers, with their input axes
BLOCK_IN_AXES = {"q": (0,), "k": (0,), "v": (0,), "o": (0, 1),
                 "up": (0,), "down": (0,), "ada": (0,)}


def weights(cfg: dict, key, bits: int | None = None) -> dict:
    x = dims(cfg)
    d, h, hd, ff = x["d"], x["h"], x["hd"], x["ff"]
    p2c = x["p"] ** 2 * x["c"]

    def block(bk):
        ka, km, kc = jax.random.split(bk, 3)
        kq, kk, kv, ko = jax.random.split(ka, 4)
        k1, k2, _ = jax.random.split(km, 3)
        sd = 1.0 / math.sqrt(d)
        return {"q": _tn(kq, (d, h, hd), sd), "k": _tn(kk, (d, h, hd), sd),
                "v": _tn(kv, (d, h, hd), sd),
                "o": _tn(ko, (h * hd, d), 1.0 / math.sqrt(h * hd)
                         ).reshape(h, hd, d),
                "up": _tn(k1, (d, ff), sd),
                "down": _tn(k2, (ff, d), 1.0 / math.sqrt(ff)),
                "ada": _tn(kc, (d, 6 * d), sd)}

    @jax.jit
    def make(k):
        keys = jax.random.split(k, 7)
        w = {"patch": _tn(keys[0], (p2c, d), 1.0 / math.sqrt(p2c)),
             "t1": _tn(keys[1], (x["freq"], d), 1.0 / math.sqrt(x["freq"])),
             "t2": _tn(keys[2], (d, d), 1.0 / math.sqrt(d)),
             "table": _tn(keys[3], (x["classes"] + 1, d), 0.02),
             "fada": _tn(keys[4], (d, 2 * d), 1.0 / math.sqrt(d)),
             "flin": _tn(keys[5], (d, x["out"]), 1.0 / math.sqrt(d)),
             "blocks": jax.vmap(block)(
                 jax.random.split(keys[6], x["layers"]))}
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        if bits:
            w["blocks"] = {n: fake_quant(a, tuple(i + 1 for i in
                                                  BLOCK_IN_AXES[n]), bits)
                           for n, a in w["blocks"].items()}
        return w

    return make(key)


def _ln(x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6)


def _mod(x, shift, scale):
    return x * (1.0 + scale[:, None]) + shift[:, None]


def forward(cfg: dict, w: dict, lat, t, y, bits: int | None = None):
    """``lat`` [B, C, H, W], ``t`` [B], ``y`` [B] -> [B, out_ch, H, W].
    With ``bits`` the inputs of every planned matmul are quantized to
    that many bits per row (the weights are quantized in ``weights``)."""
    x = dims(cfg)
    p, g = x["p"], x["size"] // x["p"]
    B = lat.shape[0]
    tok = lat.reshape(B, x["c"], g, p, g, p).transpose(0, 2, 4, 3, 5, 1)
    tok = tok.reshape(B, g * g, p * p * x["c"]) @ w["patch"]
    half = x["freq"] // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    temb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], -1)
    c = jax.nn.silu(temb @ w["t1"]) @ w["t2"] + w["table"][y]
    sc = jax.nn.silu(c)

    qa = fake_quant_rows

    def block(tok, b):
        s1, c1, g1, s2, c2, g2 = jnp.split(qa(sc, bits) @ b["ada"], 6, -1)
        hm = qa(_mod(_ln(tok), s1, c1), bits)
        q = jnp.einsum("btd,dhk->bthk", hm, b["q"])
        k = jnp.einsum("btd,dhk->bthk", hm, b["k"])
        v = jnp.einsum("btd,dhk->bthk", hm, b["v"])
        s = jnp.einsum("bqhk,bshk->bhqs", q, k) / math.sqrt(x["hd"])
        a = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v)
        a = qa(a, bits, (-2, -1))
        tok = tok + g1[:, None] * jnp.einsum("bthk,hkd->btd", a, b["o"])
        hm = qa(_mod(_ln(tok), s2, c2), bits)
        mlp = qa(jax.nn.gelu(hm @ b["up"], approximate=True), bits)
        mlp = mlp @ b["down"]
        return tok + g2[:, None] * mlp, None

    tok, _ = jax.lax.scan(block, tok, w["blocks"])
    shift, scale = jnp.split(sc @ w["fada"], 2, -1)
    out = _mod(_ln(tok), shift, scale) @ w["flin"]
    oc = x["out"] // (p * p)
    out = out.reshape(B, g, g, p, p, oc).transpose(0, 5, 1, 3, 2, 4)
    return out.reshape(B, oc, x["size"], x["size"])


def schedule(num_steps: int):
    betas = np.linspace(1e-4, 0.02, 1000, dtype=np.float64)
    ab = np.cumprod(1.0 - betas)
    ts = np.round(np.linspace(999, 0, num_steps)).astype(np.int64)
    return ab, ts


def sample(cfg: dict, w: dict, noise, labels, num_steps: int,
           cfg_scale: float, bits: int | None = None):
    """DDIM with classifier-free guidance from ``noise`` [B, C, H, W]."""
    x = dims(cfg)
    ab, ts = schedule(num_steps)
    null = jnp.full_like(labels, x["classes"])

    @jax.jit
    def eps_of(w, lat, t):
        tt = jnp.full((2 * lat.shape[0],), t, jnp.int32)
        out = forward(cfg, w, jnp.concatenate([lat, lat]), tt,
                      jnp.concatenate([labels, null]), bits)[:, :x["c"]]
        ec, eu = jnp.split(out, 2)
        return eu + cfg_scale * (ec - eu)

    lat = noise.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for i, t in enumerate(ts):
            ab_t = float(ab[t])
            ab_prev = float(ab[ts[i + 1]]) if i + 1 < len(ts) else 1.0
            eps = eps_of(w, lat, jnp.int32(t))
            x0 = (lat - np.sqrt(1.0 - ab_t) * eps) / np.sqrt(ab_t)
            lat = np.sqrt(ab_prev) * x0 + np.sqrt(1.0 - ab_prev) * eps
    return lat


def noise(cfg: dict, seed: int, uid: int):
    """The initial latent the serving engine draws for request ``uid``
    of seed ``seed``: a standard normal keyed by ``fold_in(seed, uid)``."""
    x = dims(cfg)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), uid)
    return jax.random.normal(key, (x["c"], x["size"], x["size"]),
                             jnp.float32)
