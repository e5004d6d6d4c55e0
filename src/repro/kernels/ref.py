"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def cim_gemm_int8_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    """int8 [M,K] @ int8 [K,N] -> int32."""
    return jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def quantize_rows_int8_ref(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Dynamic per-row symmetric int8: x [M, K] -> (q, scale [M, 1])."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True) + 1e-12
    # a multiply: under jit XLA turns ``/ 127.0`` into this multiply, so
    # only this form gives the same scale eagerly and jitted
    scale = amax * (1.0 / 127.0)
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantized_matmul_ref(x: jax.Array, w_q: jax.Array,
                         w_scale: jax.Array) -> jax.Array:
    """bf16/f32 activations x per-channel-int8 weights (dequant ref)."""
    x_q, x_scale = quantize_rows_int8_ref(x)
    acc = cim_gemm_int8_ref(x_q, w_q).astype(jnp.float32)
    return acc * x_scale * w_scale[None, :]


def _activate_ref(x: jax.Array, activation: str | None) -> jax.Array:
    if activation is None:
        return x
    if activation == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if activation == "silu":
        return jax.nn.silu(x)
    if activation == "relu":
        return jax.nn.relu(x)
    raise ValueError(activation)


def fused_matmul_ref(x: jax.Array, w_q: jax.Array, w_scale: jax.Array,
                     bias: jax.Array | None = None,
                     residual: jax.Array | None = None,
                     activation: str | None = None,
                     out_dtype=jnp.float32) -> jax.Array:
    """Oracle for the fused epilogue: quant -> GEMM -> dequant/bias/act
    (+ fused residual add)."""
    x_q, x_scale = quantize_rows_int8_ref(x)
    out = cim_gemm_int8_ref(x_q, w_q).astype(jnp.float32)
    out = out * x_scale * w_scale[None, :]
    if bias is not None:
        out = out + bias[None, :]
    out = _activate_ref(out, activation)
    if residual is not None:
        out = out + residual.astype(jnp.float32)
    return out.astype(out_dtype)


def gated_mlp_hidden_ref(x: jax.Array, g_q: jax.Array, g_scale: jax.Array,
                         u_q: jax.Array, u_scale: jax.Array,
                         activation: str = "gelu") -> jax.Array:
    """Oracle for the fused gated front half: act(x@Wg) * (x@Wu), f32."""
    x_q, x_scale = quantize_rows_int8_ref(x)
    g = cim_gemm_int8_ref(x_q, g_q).astype(jnp.float32) * x_scale \
        * g_scale[None, :]
    u = cim_gemm_int8_ref(x_q, u_q).astype(jnp.float32) * x_scale \
        * u_scale[None, :]
    return _activate_ref(g, activation) * u


def quantized_mlp_ref(x: jax.Array, qtree: dict, activation: str,
                      residual: jax.Array | None = None,
                      out_dtype=jnp.float32) -> jax.Array:
    """End-to-end oracle for the fused int8 MLP pipeline.

    ``qtree``: {'up': (q, scale)[, 'gate': ...], 'down': (q, scale)}.
    ``activation`` is a canonical kernel name ("gelu"|"silu"|"relu");
    quant/linear.py owns the geglu/swiglu alias mapping.  Mirrors the
    kernel pipeline exactly, including the int8 requant of the hidden
    state between the two GEMMs and the residual add fused into the
    down GEMM's epilogue.
    """
    if "gate" in qtree:
        h = gated_mlp_hidden_ref(x, qtree["gate"][0], qtree["gate"][1],
                                 qtree["up"][0], qtree["up"][1], activation)
    else:
        h = fused_matmul_ref(x, qtree["up"][0], qtree["up"][1],
                             activation=activation)
    h_q, h_scale = quantize_rows_int8_ref(h)
    out = cim_gemm_int8_ref(h_q, qtree["down"][0]).astype(jnp.float32)
    out = out * h_scale * qtree["down"][1][None, :]
    if residual is not None:
        out = out + residual.astype(jnp.float32)
    return out.astype(out_dtype)


def grouped_quantized_mlp_ref(x: jax.Array, qtree: dict, activation: str,
                              out_dtype=jnp.float32,
                              groups: jax.Array | None = None) -> jax.Array:
    """Oracle for the grouped-expert fused int8 MLP pipeline.

    x [E, T, d]; ``qtree`` holds stacked per-expert leaves:
    {'up': (q [E, d, F], scale [E, F])[, 'gate': ...],
     'down': (q [E, F, d'], scale [E, d'])}.  Exactly
    :func:`quantized_mlp_ref` vmapped over the expert axis — the grouped
    Pallas kernel must match this (and hence the per-expert loop)
    bit-for-bit, since every step is elementwise or exact int32 math.
    With ``groups`` (int32 [T]) x is [T, M, d] row tiles and tile t
    takes expert ``groups[t]``'s leaves (the ragged form).
    """
    if groups is not None:
        qtree = jax.tree.map(lambda a: a[groups], qtree)
    return jax.vmap(
        lambda xe, qt: quantized_mlp_ref(xe, qt, activation,
                                         out_dtype=out_dtype))(x, qtree)


def flash_attention_ref(q, k, v, causal=True, window=None):
    """Dense attention oracle; q [B,S,H,D], k/v [B,S,KH,D]."""
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    qg = q.reshape(B, Sq, KH, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    s = s / math.sqrt(D)
    qp = jnp.arange(Sq)[:, None]
    kp = jnp.arange(k.shape[1])[None, :]
    ok = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= kp > qp - window
    s = jnp.where(ok, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o.reshape(B, Sq, H, D)


def decode_attention_ref(q, k, v, pos, q_pos, window=None,
                         k_scale=None, v_scale=None):
    """q [B,KH,G,D]; k/v [B,S,KH,D]; pos [B,S]; q_pos [B].

    ``k_scale``/``v_scale`` [B,S,KH] f32 dequantize an int8 KV cache
    (the XLA oracle for the kernel's in-kernel dequant path)."""
    B, KH, G, D = q.shape
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[..., None]
        v = v.astype(jnp.float32) * v_scale[..., None]
        q = q.astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", q, k).astype(jnp.float32)
    s = s / math.sqrt(D)
    ok = pos[:, None, None, :] <= q_pos[:, None, None, None]
    if window is not None:
        ok &= pos[:, None, None, :] > (q_pos[:, None, None, None] - window)
    s = jnp.where(ok, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgs,bshd->bhgd", p.astype(v.dtype), v)


def decode_attention_paged_ref(q, k_pages, v_pages, pos_pages, block_tables,
                               q_pos, layer, window=None, k_scale_pages=None,
                               v_scale_pages=None):
    """Oracle for the paged (block-table) flash-decode kernel: gather
    layer ``layer`` of the stacked pools into the linear [B, nb*bs, KH,
    D] layout and run the dense decode reference.  q [B,KH,G,D]; pools
    [L,NB,bs,KH,D]; pos_pages [L,NB,bs]; block_tables [B,nb] int32 (0 =
    reserved null block, all empty-sentinel, so unallocated entries
    self-mask); layer an int32 scalar."""
    B, nb = block_tables.shape
    bs = pos_pages.shape[2]
    bt = block_tables.astype(jnp.int32)
    k = k_pages[layer, bt].reshape(B, nb * bs, *k_pages.shape[3:])
    v = v_pages[layer, bt].reshape(B, nb * bs, *v_pages.shape[3:])
    pos = pos_pages[layer, bt].reshape(B, nb * bs)
    ks = vs = None
    if k_scale_pages is not None:
        ks = k_scale_pages[layer, bt].reshape(B, nb * bs, -1)
        vs = v_scale_pages[layer, bt].reshape(B, nb * bs, -1)
    return decode_attention_ref(q, k, v, pos, q_pos, window=window,
                                k_scale=ks, v_scale=vs)


def ssd_scan_ref(x, log_a, b, c):
    """Naive recurrence. x [BH,S,P]; log_a [BH,S]; b/c [BH,S,N]."""
    BH, S, P = x.shape
    N = b.shape[-1]

    def step(h, inputs):
        xt, lat, bt, ct = inputs
        h = jnp.exp(lat)[:, None, None] * h + \
            jnp.einsum("gp,gn->gpn", xt, bt)
        y = jnp.einsum("gpn,gn->gp", h, ct)
        return h, y

    h0 = jnp.zeros((BH, P, N), jnp.float32)
    h, ys = jax.lax.scan(
        step, h0,
        (x.swapaxes(0, 1), log_a.swapaxes(0, 1), b.swapaxes(0, 1),
         c.swapaxes(0, 1)))
    return ys.swapaxes(0, 1), h


def online_softmax_ref(x):
    return jax.nn.softmax(x.astype(jnp.float32), axis=-1).astype(x.dtype)


def mla_decode_paged_ref(q_lat, q_rope, latent_pages, c_scale_pages,
                         r_scale_pages, block_tables, q_pos, layer,
                         scale: float):
    """Oracle for ``mla_decode_paged``: the same bf16 MXU operands (the
    dequant scales applied to the f32 products) over the row's gathered
    latents at layer ``layer``; rows at or past the empty sentinel
    (2**29) attend nothing and return zeros."""
    B, nb = block_tables.shape
    bs = latent_pages.shape[2]
    R, Dr = q_lat.shape[-1], q_rope.shape[-1]
    bt = block_tables.astype(jnp.int32)

    def lin(pool):
        g = pool[layer, bt]                       # [B, nb, bs, ...]
        return g.reshape(B, nb * bs, *pool.shape[3:])

    lat = lin(latent_pages).astype(jnp.bfloat16)
    c, kr = lat[..., :R], lat[..., R:R + Dr]
    sc, sr = lin(c_scale_pages), lin(r_scale_pages)       # [B, T]
    ql = q_lat.astype(jnp.bfloat16)
    qr = q_rope.astype(jnp.bfloat16)
    s = (jnp.einsum("bhr,btr->bht", ql, c,
                    preferred_element_type=jnp.float32) * sc[:, None]
         + jnp.einsum("bhk,btk->bht", qr, kr,
                      preferred_element_type=jnp.float32) * sr[:, None])
    s = s * scale
    qpos = jnp.where(q_pos < 2 ** 29, q_pos, -1)
    t = jnp.arange(nb * bs)
    ok = t[None, None, :] <= qpos[:, None, None]
    s = jnp.where(ok, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bht,btr->bhr", (p * sc[:, None]).astype(jnp.bfloat16), c,
                   preferred_element_type=jnp.float32)
    return (o / jnp.maximum(l, 1e-30)).astype(q_lat.dtype)


def mla_prefill_paged_ref(q_lat, q_rope, latent_pages, c_scale_pages,
                          r_scale_pages, block_tables, positions, layer,
                          scale: float):
    """Oracle for ``mla_prefill_paged``: :func:`mla_decode_paged_ref` for
    each query position of the chunk (q_lat [B, S, H, r], positions [B,
    S]); returns [B, S, H, r]."""
    one = functools.partial(mla_decode_paged_ref, latent_pages=latent_pages,
                            c_scale_pages=c_scale_pages,
                            r_scale_pages=r_scale_pages,
                            block_tables=block_tables, layer=layer,
                            scale=scale)
    return jax.vmap(lambda ql, qr, qp: one(ql, qr, q_pos=qp),
                    in_axes=(1, 1, 1), out_axes=1)(q_lat, q_rope, positions)
