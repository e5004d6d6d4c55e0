"""Work of one DiT denoise evaluation (adaLN blocks, full attention).

Per block the int8 plan runs the adaLN modulation GEMM (one row per
latent), the fused QKV projection, the out-projection and the two MLP
linears over every token.  Patch embedding, the timestep and label
embedders and the final layer stay bf16.  Returns ``{family: [(count,
call), ...]}`` like ``llama.py``.
"""
from __future__ import annotations

from bench.ops import attention, gemm


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    tokens = (cfg["input_size"] // cfg["patch_size"]) ** 2
    return {"d": d, "h": cfg["num_heads"], "hd": d // cfg["num_heads"],
            "ff": int(cfg["mlp_ratio"] * d), "t": tokens,
            "layers": cfg["depth"], "p2c": cfg["patch_size"] ** 2
            * cfg["in_channels"], "freq": cfg["frequency_embedding_size"],
            "out": cfg["patch_size"] ** 2 * cfg["in_channels"]
            * (2 if cfg["learn_sigma"] else 1)}


def evaluation(cfg: dict, rows: int) -> dict:
    """One forward over ``rows`` latents (CFG rows included)."""
    x = dims(cfg)
    m, n = rows * x["t"], x["layers"]
    return {
        "gemm": [(n, gemm.int8_linear(rows, x["d"], 6 * x["d"])),
                 (n, gemm.int8_linear(m, x["d"], 3 * x["d"])),
                 (n, gemm.int8_linear(m, x["d"], x["d"])),
                 (n, gemm.int8_linear(m, x["d"], x["ff"])),
                 (n, gemm.int8_linear(m, x["ff"], x["d"]))],
        "attn": [(rows * n, attention.full(x["t"], x["h"], x["hd"]))],
        "head": [(1, gemm.bf16_linear(m, x["p2c"], x["d"])),
                 (1, gemm.bf16_linear(rows, x["freq"], x["d"])),
                 (1, gemm.bf16_linear(rows, x["d"], x["d"])),
                 (1, gemm.bf16_linear(rows, x["d"], 2 * x["d"])),
                 (1, gemm.bf16_linear(m, x["d"], x["out"]))]}
