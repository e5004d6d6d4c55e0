"""Device: share of the traced window in which no operation ran."""
from bench.lib import trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.traced_ns
    if hi <= lo:
        return None
    busy = trace.busy_ns(run.trace.ops, lo, hi, run.trace.n_devices)
    return 100.0 * (1.0 - busy / (hi - lo))
