"""The expert layer's grouped matmuls in decode: their least time
(``counts_mla_moe.experts_least``: the weights of the held experts each
tick touched, the FLOPs of its held picks, as the engine's commit span
counts them) over the device time of the ``ragged-dot`` operations inside
the traced decode ticks, in %.  Nothing where the commit spans carry no
expert counters or no such operation ran."""
import re

import numpy as np

from benchmarks.chip import counts_mla_moe as C
from benchmarks.chip import trace as T

KERNEL = re.compile(r"ragged.dot")


def read(run):
    if run.trace is None:
        return None
    kernels = [T.merge(np.asarray([(s, e) for s, e, n in d.ops
                                   if KERNEL.search(T.op_kind(n))]
                                  ).reshape(-1, 2))
               for d in run.trace.devices]
    least = busy = 0.0
    for s, t in run.traced_ticks(prefill=False):
        counters = C.expert_counters(run, s)
        if counters is None:
            continue
        routed, touched = counters
        least += C.experts_least(run.src, run.peak, touched, routed)
        busy += float(np.mean([T.covered(k, s.start, s.end)
                               for k in kernels]))
    return 100.0 * least / busy if busy > 0 else None
