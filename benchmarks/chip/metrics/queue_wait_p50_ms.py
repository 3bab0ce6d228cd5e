"""Scheduler / admission: median wait from a request's due time to the end
of the ``Engine.step`` after which it holds a slot (host clock)."""
import numpy as np


def read(run):
    waits = [(r.admitted - r.due) * 1e3 for r in run.recs
             if r.admitted is not None and run.t0 <= r.due <= run.t1]
    return float(np.median(waits)) if waits else None
