"""Host path: the engine's host sampling per decode step -- the self
time of its ``engine.decode.sample`` spans in the window (slot releases
and garbage collections nested in them left out), over their number.
The device waits through it: the next step cannot dispatch until it
ends."""
from bench.lib import engine_spans


def read(run):
    spans = engine_spans.of_run(run)
    lo, hi = run.traced_ns
    samples = engine_spans.inside(spans, lo, hi, "engine.decode.sample")
    if not samples:
        return None
    nested = sorted(sp for sp in engine_spans.inside(spans, lo, hi)
                    if sp[2] in engine_spans.NESTED)
    self_ns = sum(e - s - engine_spans.covered_ns((s, e), nested)
                  for s, e, _ in samples)
    return self_ns / len(samples) / 1e6
