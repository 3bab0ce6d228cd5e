"""Fused model step at S = 1, paged gather/scatter included: device busy
time inside the traced ``serve.step`` spans whose tick fed no prompt
tokens, mean, in ms."""
import numpy as np


def read(run):
    pairs = run.traced_ticks(prefill=False)
    if not pairs:
        return None
    return float(np.mean([run.trace.busy_in(s.start, s.end)
                          for s, _ in pairs])) * 1e3
