"""One int8 linear layer ``[M, K] @ [K, N]`` on the fused pipeline."""
from __future__ import annotations


def int8_linear(m: int, k: int, n: int) -> dict:
    """Ops and least bytes of one quantized linear.

    Ops: ``2*M*K*N`` int8 multiply-adds.  Bytes: the int8 weight once,
    its f32 per-column scale, and one byte per activation element in
    and out (the pipeline's int8 activations; any wider type moves
    more), so the bound holds for every tiling and epilogue.
    """
    return {"int8_ops": 2.0 * m * k * n, "bf16_ops": 0.0,
            "bytes": float(k * n + 4 * n + m * k + m * n)}


def gated_int8(m: int, k: int, n: int) -> dict:
    """The gate and up linears in one call: both weights are read, and
    ``act(x Wg) * (x Wu)`` is written once."""
    return {"int8_ops": 4.0 * m * k * n, "bf16_ops": 0.0,
            "bytes": float(2 * k * n + 8 * n + m * k + m * n)}


def bf16_linear(m: int, k: int, n: int) -> dict:
    """A bf16 matmul (LM head, embeddings the plan leaves unquantized)."""
    return {"int8_ops": 0.0, "bf16_ops": 2.0 * m * k * n,
            "bytes": float(2 * (k * n + m * k + m * n))}


def compute_s(c: dict, peak: dict) -> float:
    """Time at the peak rate of each operand type."""
    return (c["int8_ops"] / peak["int8_ops_per_s"]
            + c["bf16_ops"] / peak["bf16_flops_per_s"])


def least_s(c: dict, peak: dict) -> float:
    """The least time one call could take: the larger of its compute at
    the peaks and its bytes at the memory bandwidth."""
    return max(compute_s(c, peak), c["bytes"] / peak["hbm_bytes_per_s"])


def calls_least_s(calls, peak: dict) -> float:
    """Least time of ``[(count, call), ...]``, call by call."""
    return sum(n * least_s(c, peak) for n, c in calls)


def calls_compute_s(calls, peak: dict) -> float:
    return sum(n * compute_s(c, peak) for n, c in calls)
