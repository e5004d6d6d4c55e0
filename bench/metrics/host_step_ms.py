"""Host path: the host's own work per engine step -- each
``engine.step`` span in the window less the part its
``engine.*.fetch`` spans cover (the host blocked on the device and the
copy of the results), averaged over steps.  The device waits through
it unless work dispatched earlier in the step is still running."""
from bench.lib import engine_spans


def read(run):
    spans = engine_spans.of_run(run)
    lo, hi = run.traced_ns
    steps = engine_spans.inside(spans, lo, hi, "engine.step")
    if not steps:
        return None
    fetches = sorted(sp for sp in engine_spans.inside(spans, lo, hi)
                     if sp[2].startswith("engine.")
                     and sp[2].endswith(".fetch"))
    own = sum(e - s - engine_spans.covered_ns((s, e), fetches)
              for s, e, _ in steps)
    return own / len(steps) / 1e6
