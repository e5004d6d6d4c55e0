"""Scheduler: median wait from a request's due time to the end of the
first engine step after which it holds a slot (host clock).  A request
still queued when the window closes counts with the time it has waited.
"""
import numpy as np


def read(run):
    if run.kind != "lm" or not run.requests:
        return None
    waits = [(r["admit"] if r["admit"] is not None
              and r["admit"] <= run.t_close else run.t_close) - r["due"]
             for r in run.requests]
    return float(np.median(waits))
