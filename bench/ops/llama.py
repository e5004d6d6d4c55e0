"""Work of one engine step of a Llama-style model (deepseek-67b).

Per layer the int8 plan runs four kernel calls: the fused QKV
projection, the out-projection, the gated up/gate pair and the down
projection.  The embedding and LM head stay bf16.  Each function
returns ``{family: [(count, call), ...]}`` with the families ``gemm``
(fused int8 kernels), ``attn`` and ``head``.
"""
from __future__ import annotations

from bench.ops import attention, gemm


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kh": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim", d // h), "ff": cfg["intermediate_size"],
            "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


def layer_gemms(cfg: dict, m: int) -> list:
    """The int8 kernel calls of every layer for ``m`` token rows."""
    x = dims(cfg)
    n = x["layers"]
    return [(n, gemm.int8_linear(m, x["d"], (x["h"] + 2 * x["kh"]) * x["hd"])),
            (n, gemm.int8_linear(m, x["h"] * x["hd"], x["d"])),
            (n, gemm.gated_int8(m, x["d"], x["ff"])),
            (n, gemm.int8_linear(m, x["ff"], x["d"]))]


def decode(cfg: dict, ctx_lens) -> dict:
    """One batched decode call: rows attend to ``ctx_lens`` positions."""
    x = dims(cfg)
    m = len(ctx_lens)
    return {"gemm": layer_gemms(cfg, m),
            "attn": [(x["layers"], attention.paged_decode(
                ctx_lens, x["h"], x["kh"], x["hd"]))],
            "head": [(1, gemm.bf16_linear(m, x["d"], x["v"]))]}


def prefill_chunk(cfg: dict, n_valid: int, offset: int, last: bool) -> dict:
    """One chunk of ``n_valid`` prompt tokens at ``offset``; the head's
    logits are useful only after the prompt's last chunk."""
    x = dims(cfg)
    return {"gemm": layer_gemms(cfg, n_valid),
            "attn": [(x["layers"], attention.causal_prefill(
                n_valid, offset, x["h"], x["hd"]))],
            "head": [(1 if last else 0,
                      gemm.bf16_linear(1, x["d"], x["v"]))]}
