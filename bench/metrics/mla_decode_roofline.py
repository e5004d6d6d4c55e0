"""Kernels: the paged latent (MLA) decode kernel's share of its
roofline (least time of the traced ``mla_decode_paged`` calls, from
``bench/ops``, over the kernel's device time)."""
from bench.metrics import gemm_roofline

PATTERNS = ("mla_decode_paged",)


def read(run):
    if run.kind != "lm":
        return None
    return gemm_roofline.read(run, family="mla", patterns=PATTERNS)
