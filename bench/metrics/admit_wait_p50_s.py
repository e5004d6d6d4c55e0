"""Scheduler: median wait from a request's due time to its admission
to a slot -- the harness's submission lag (submit - due, host clock)
plus the engine's queue wait (``Request.admitted_at`` -
``submitted_at``, engine clock), each difference on its own clock.  A
request still queued when the window closes counts the wait to the
close.  A program whose requests carry no admission time gives
nothing."""
import numpy as np


def read(run):
    if run.kind != "lm" or not run.requests \
            or not hasattr(run.requests[0]["req"], "admitted_at"):
        return None
    waits = []
    for r in run.requests:
        req = r["req"]
        to_close = run.t_close - r["due"]
        if req.admitted_at is None:
            waits.append(to_close)
        else:
            waits.append(min(to_close, r["submit"] - r["due"]
                             + req.admitted_at - req.submitted_at))
    return float(np.median(waits))
