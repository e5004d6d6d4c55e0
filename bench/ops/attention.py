"""Attention calls: paged int8-KV decode and causal prefill."""
from __future__ import annotations


def paged_decode(ctx_lens, n_heads: int, n_kv_heads: int,
                 head_dim: int) -> dict:
    """One decode-attention call over rows with ``ctx_lens`` cached
    positions each (the new token included).

    Ops: ``QK^T`` and ``PV``, ``4*H*D`` per attended position, at the
    bf16 peak (the kernel dequantizes int8 K/V).  Bytes: each row's
    int8 K and V, their f32 per-head scales and int32 positions, plus
    the bf16 query and output.
    """
    total = float(sum(ctx_lens))
    rows = len(ctx_lens)
    per_pos = n_kv_heads * (2 * head_dim + 2 * 4) + 4
    return {"int8_ops": 0.0,
            "bf16_ops": 4.0 * n_heads * head_dim * total,
            "bytes": per_pos * total + 2 * 2.0 * rows * n_heads * head_dim}


def causal_prefill(n_q: int, offset: int, n_heads: int,
                   head_dim: int) -> dict:
    """Useful attention ops of ``n_q`` queries at positions
    ``offset..offset+n_q-1``, each attending to itself and all before."""
    keys = n_q * offset + n_q * (n_q + 1) / 2
    return {"int8_ops": 0.0, "bf16_ops": 4.0 * n_heads * head_dim * keys,
            "bytes": 0.0}


def full(n_tokens: int, n_heads: int, head_dim: int) -> dict:
    """Bidirectional attention over ``n_tokens`` (DiT)."""
    return {"int8_ops": 0.0,
            "bf16_ops": 4.0 * n_heads * head_dim * n_tokens * n_tokens,
            "bytes": 0.0}
