"""Kernels: the grouped int8 expert GEMMs' share of their roofline
(least time of the traced expert calls, from ``bench/ops``, over the
device time of the ``cim_grouped_*`` kernels).  The row quantizer that
feeds them is the dense pipeline's kernel and counts under
``gemm_roofline``."""
from bench.metrics import gemm_roofline

PATTERNS = ("cim_grouped_",)


def read(run):
    if run.kind != "lm":
        return None
    return gemm_roofline.read(run, family="moe", patterns=PATTERNS)
