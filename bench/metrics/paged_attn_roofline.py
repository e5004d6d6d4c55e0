"""Kernels: the paged int8-KV decode attention kernel's share of its
roofline (least time of the traced calls over the kernel's time)."""
from bench.metrics import gemm_roofline

PATTERNS = ("decode_attention_paged",)


def read(run):
    if run.kind != "lm":
        return None
    return gemm_roofline.read(run, family="attn", patterns=PATTERNS)
