"""Kernels: the fused INT8 GEMM pipeline's share of its roofline.

Sum of each traced call's least time (``bench/ops``, logical shapes)
over the device time of the pipeline's Mosaic kernels in the trace:
the fused and gated GEMMs and the row quantizer that feeds them."""
from bench.lib import trace
from bench.ops import gemm

PATTERNS = ("cim_gemm_int8", "cim_gated_gemm_int8", "quantize_rows_int8")


def read(run, family="gemm", patterns=PATTERNS):
    if run.trace is None:
        return None
    work = run.traced_work()
    if any(w is None for w in work):
        return None
    least = gemm.calls_least_s(
        [c for w in work for c in w.get(family, [])], run.peaks)
    lo, hi = run.traced_ns
    ns, n = trace.kernel_ns(run.trace.ops, patterns, lo, hi)
    if not n or least <= 0:
        return None
    return 100.0 * least / (ns / 1e9)
