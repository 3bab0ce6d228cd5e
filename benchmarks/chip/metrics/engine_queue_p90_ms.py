"""Scheduler and admission, from the engine's own request stamps: the time
from ``Engine.submit`` to the request's first slot assignment (``t_admit -
t_submit``), p90 over the requests due inside the window, in ms; one still
queued at the close counts its wait to the close (host clock).  Nothing
for a program whose requests carry no stamps."""
import numpy as np


def read(run):
    due = [r.req for r in run.recs
           if run.t0 <= r.due <= run.t1 and r.req.error is None]
    if not due or getattr(due[0], "t_submit", None) is None:
        return None
    waits = [(min(q.t_admit if q.t_admit is not None else run.t1, run.t1)
              - q.t_submit) * 1e3 for q in due]
    return float(np.percentile(waits, 90))
