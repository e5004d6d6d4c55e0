"""Model step: device time of the prefill-chunk programs in the trace
(the jitted ``prefill_chunk`` modules), per chunk."""
from bench.lib import trace


def read(run):
    if run.kind != "lm" or run.trace is None:
        return None
    lo, hi = run.traced_ns
    ns, n = trace.module_ns(run.trace.modules, "prefill_chunk", lo, hi)
    return ns / n / 1e6 if n else None
