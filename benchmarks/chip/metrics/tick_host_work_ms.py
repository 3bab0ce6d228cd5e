"""Engine host path, from the engine's own spans: inside each traced
``serve.step``, the time covered by its ``serve.engine.*`` spans other
than ``serve.engine.sync`` (admit, compose, upload, dispatch, commit,
release; nested spans counted once), median over the traced ticks, in ms.
Nothing where the trace holds no engine span."""
import numpy as np

from benchmarks.chip import trace as T

PREFIX = "serve.engine."
SYNC = "serve.engine.sync"


def read(run):
    if run.trace is None:
        return None
    work = np.asarray([(s.start, s.end) for s in run.trace.spans
                       if s.name.startswith(PREFIX) and s.name != SYNC])
    steps = run.trace.spans_named("serve.step")
    if not len(work) or not steps:
        return None
    merged = T.merge(work.reshape(-1, 2))
    return float(np.median([T.covered(merged, s.start, s.end)
                            for s in steps])) * 1e3
