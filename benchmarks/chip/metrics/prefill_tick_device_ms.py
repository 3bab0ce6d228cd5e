"""Fused model step at S = prefill chunk: device busy time inside the traced
``serve.step`` spans whose tick fed prompt tokens, mean, in ms."""
import numpy as np


def read(run):
    pairs = run.traced_ticks(prefill=True)
    if not pairs:
        return None
    return float(np.mean([run.trace.busy_in(s.start, s.end)
                          for s, _ in pairs])) * 1e3
