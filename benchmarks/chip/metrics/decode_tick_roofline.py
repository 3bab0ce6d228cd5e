"""The decode tick as one kernel: the least time of the work the algorithm
needs (``counts.decode_tick_least``: weights, each slot's live K/V, the new
K/V, the FLOPs, at the chip's published peaks; memory bound at these sizes)
over the device busy time of the traced decode ticks, in %."""
from benchmarks.chip import counts


def read(run):
    pairs = [(s, t) for s, t in run.traced_ticks(prefill=False)
             if t.contexts]
    busy = sum(run.trace.busy_in(s.start, s.end) for s, _ in pairs)
    if not pairs or busy <= 0:
        return None
    least = sum(counts.decode_tick_least(run.src, run.peak, t.contexts)[0]
                for _, t in pairs)
    return 100.0 * least / busy
