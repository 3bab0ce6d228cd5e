"""Engine host path: device idle inside each ``serve.step`` span (compose,
block-table upload, dispatch, the closing host sync), median over the
traced ticks, in ms."""
import numpy as np


def read(run):
    pairs = run.traced_ticks()
    if not pairs:
        return None
    gaps = [(s.end - s.start) - run.trace.busy_in(s.start, s.end)
            for s, _ in pairs]
    return float(np.median(gaps)) * 1e3
