"""Work of one engine step of DeepSeek-V3 with one expert-parallel share
of the routed experts.

Families, each ``[(count, call), ...]``:

* ``gemm``: the calls on ``gemm_roofline``'s kernels (fused and gated
  int8 GEMMs): per layer MLA's q_a|kv_a (one wide GEMM), q_b and
  out-projection; the dense FFN in the first layers, the shared expert
  in the MoE layers.
* ``moe``: the grouped expert kernels (``cim_grouped_*``).  Under
  uniform routing each of ``m`` token rows picks each expert with
  probability ``k/E``, so the held experts get ``m*k*E_held/E`` rows and
  ``E_held * (1 - (1 - k/E)**m)`` of them get any: at m = 32, 8 of 256
  experts per token and 8 held, about 5.1 of the 8.  Only touched
  experts' weights count as bytes; the kernel that streams all 8 reads
  as below its roofline, as it should.
* ``mla``: the latent decode kernel ``mla_decode_paged``.  bf16 MXU
  operands (the int8 latent converts in-kernel): per attended position
  ``2*H*(r + rope)`` score and ``2*H*r`` output flops against
  ``r + rope`` int8 bytes and two f32 scales.
* ``mla_prefill``: the latent chunked-prefill kernel
  ``mla_prefill_paged``, the same absorbed products for each of the
  chunk's queries over the positions it sees.
* ``mla_xla``: MLA work XLA runs outside the kernels, in bf16: the
  W_UK / W_UV folds around both kernels.
* ``head``: the bf16 LM head.

Counts use logical shapes.  ``mfu`` reads every family.
"""
from __future__ import annotations

from bench.ops import gemm


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "q_lora": cfg["q_lora_rank"], "r": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "ff": cfg["intermediate_size"],
            "F": cfg["moe_intermediate_size"], "E": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"],
            "held": cfg["n_routed_experts_held"],
            "shared": cfg["n_shared_experts"],
            "layers": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"],
            "vocab": cfg["vocab_size"]}


def expected_experts(x: dict, m: int) -> tuple[float, float]:
    """(rows routed to held experts, held experts touched) for ``m``
    token rows under uniform routing."""
    p = x["k"] / x["E"]
    return m * p * x["held"], x["held"] * (1.0 - (1.0 - p) ** m)


def grouped(rows: float, touched: float, k: int, n: int,
            gated: bool) -> dict:
    """One grouped int8 expert GEMM over ``rows`` routed rows in total,
    ``touched`` experts' ``[k, n]`` weights (two with ``gated``)."""
    w = 2 if gated else 1
    return {"int8_ops": 2.0 * w * rows * k * n, "bf16_ops": 0.0,
            "bytes": float(touched * w * (k * n + 4 * n) + rows * (k + n))}


def bf16_matmul(m: float, k: int, n: int) -> dict:
    """A bf16 product run by XLA (its bytes are left to the kernels)."""
    return {"int8_ops": 0.0, "bf16_ops": 2.0 * m * k * n, "bytes": 0.0}


def layer_gemms(cfg: dict, m: int) -> list:
    x = dims(cfg)
    n_dense = min(x["dense"], x["layers"])
    n_moe = x["layers"] - n_dense
    Fs = x["F"] * x["shared"]
    return [(x["layers"], gemm.int8_linear(m, x["d"],
                                           x["q_lora"] + x["r"] + x["rope"])),
            (x["layers"], gemm.int8_linear(m, x["q_lora"],
                                           x["h"] * (x["nope"] + x["rope"]))),
            (x["layers"], gemm.int8_linear(m, x["h"] * x["v"], x["d"])),
            (n_dense, gemm.gated_int8(m, x["d"], x["ff"])),
            (n_dense, gemm.int8_linear(m, x["ff"], x["d"])),
            (n_moe, gemm.gated_int8(m, x["d"], Fs)),
            (n_moe, gemm.int8_linear(m, Fs, x["d"]))]


def moe_calls(cfg: dict, m: int) -> list:
    x = dims(cfg)
    rows, touched = expected_experts(x, m)
    n_moe = x["layers"] - min(x["dense"], x["layers"])
    return [(n_moe, grouped(rows, touched, x["d"], x["F"], gated=True)),
            (n_moe, grouped(rows, touched, x["F"], x["d"], gated=False))]


def latent_decode(cfg: dict, ctx_lens) -> dict:
    """One ``mla_decode_paged`` call: rows attend ``ctx_lens`` positions
    (the new token included)."""
    x = dims(cfg)
    total = float(sum(ctx_lens))
    H, r, rope = x["h"], x["r"], x["rope"]
    return {"int8_ops": 0.0,
            "bf16_ops": 2.0 * H * total * (r + rope) + 2.0 * H * total * r,
            "bytes": total * (r + rope + 8)
            + 2.0 * len(ctx_lens) * H * (2 * r + rope)}


def latent_prefill(cfg: dict, n_valid: int, offset: int) -> dict:
    """One ``mla_prefill_paged`` call: ``n_valid`` queries at ``offset``
    onward attend causally (the chunk's own positions included); the
    least bytes read the row's context once."""
    x = dims(cfg)
    H, r, rope = x["h"], x["r"], x["rope"]
    keys = n_valid * offset + n_valid * (n_valid + 1) / 2
    return {"int8_ops": 0.0,
            "bf16_ops": 2.0 * H * keys * (r + rope) + 2.0 * H * keys * r,
            "bytes": (offset + n_valid) * (r + rope + 8)
            + 2.0 * n_valid * H * (2 * r + rope)}


def decode(cfg: dict, ctx_lens) -> dict:
    x = dims(cfg)
    m = len(ctx_lens)
    L, H = x["layers"], x["h"]
    return {"gemm": layer_gemms(cfg, m),
            "moe": moe_calls(cfg, m),
            "mla": [(L, latent_decode(cfg, ctx_lens))],
            "mla_xla": [(L, bf16_matmul(m * H, x["nope"], x["r"])),
                        (L, bf16_matmul(m * H, x["r"], x["v"]))],
            "head": [(1, gemm.bf16_linear(m, x["d"], x["vocab"]))]}


def prefill_chunk(cfg: dict, n_valid: int, offset: int, last: bool) -> dict:
    """One chunk of ``n_valid`` prompt tokens at ``offset``: the chunk's
    queries fold through W_UK, attend causally over the row's latents in
    ``mla_prefill_paged`` and fold back through W_UV; the head's logits
    are useful only after the prompt's last chunk."""
    x = dims(cfg)
    L, H = x["layers"], x["h"]
    return {"gemm": layer_gemms(cfg, n_valid),
            "moe": moe_calls(cfg, n_valid),
            "mla_prefill": [(L, latent_prefill(cfg, n_valid, offset))],
            "mla_xla": [(L, bf16_matmul(n_valid * H, x["nope"], x["r"])),
                        (L, bf16_matmul(n_valid * H, x["r"], x["v"]))],
            "head": [(1 if last else 0,
                      gemm.bf16_linear(1, x["d"], x["vocab"]))]}
