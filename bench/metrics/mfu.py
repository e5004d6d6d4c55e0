"""Whole step: useful matmul work completed in the traced window, each
operation at the peak of its operand type (int8, bf16), over the
window: the share of the chip's peak the served work used."""
from bench.ops import gemm


def read(run):
    if run.trace is None:
        return None
    work = run.traced_work()
    if not work or any(w is None for w in work):
        return None
    at_peak = sum(gemm.calls_compute_s(calls, run.peaks)
                  for w in work for calls in w.values())
    window = run.traced_s()
    return 100.0 * at_peak / window if window > 0 else None
