"""Model step: host time of the DiT batches in the window over the
denoise steps they ran (one jitted call runs all steps of a batch)."""


def read(run):
    if run.kind != "dit" or not run.steps:
        return None
    wall = sum(s["t1"] - s["t0"] for s in run.steps)
    return 1e3 * wall / sum(s["evals"] for s in run.steps)
