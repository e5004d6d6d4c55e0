"""Model step: host time of the engine steps in the window that ran no
prefill chunk, over their number.  Each such step ends in the logits
fetch, so its time covers its device work; the sum spans many steps.
"""


def read(run):
    if run.kind != "lm":
        return None
    steps = [s for s in run.steps if s["n_chunks"] == 0 and s["ctx"]]
    if not steps:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in steps) / len(steps)
