"""Fused model step, as a request's prompt sees it, from the engine's own
request stamps: the time from the first slot assignment to the first token
appended (``t_first - t_admit``), p90 over the requests due inside the
window and admitted by its close, in ms; one with no first token by the
close counts to the close (host clock).  Nothing for a program whose
requests carry no stamps."""
import numpy as np


def read(run):
    adm = [r.req for r in run.recs if run.t0 <= r.due <= run.t1
           and getattr(r.req, "t_admit", None) is not None
           and r.req.t_admit <= run.t1]
    if not adm:
        return None
    lat = [(min(q.t_first if q.t_first is not None else run.t1, run.t1)
            - q.t_admit) * 1e3 for q in adm]
    return float(np.percentile(lat, 90))
