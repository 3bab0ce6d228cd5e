"""The decode tick of a latent-attention expert model as one kernel: the
least time of the work it needs (``counts_mla_moe.decode_tick_least``:
every weight but the routed experts, the held experts the tick touched and
the FLOPs of its held picks, as the engine's commit span counts them, each
slot's cache entries at its context and the new ones) over the device busy
time of the traced decode ticks, in %.  Nothing where the commit spans
carry no expert counters."""
from benchmarks.chip import counts_mla_moe as C


def read(run):
    least = busy = 0.0
    for s, t in run.traced_ticks(prefill=False):
        counters = C.expert_counters(run, s) if t.contexts else None
        if counters is None:
            continue
        routed, touched = counters
        least += C.decode_tick_least(run.src, run.peak, t.contexts, touched,
                                     routed)
        busy += run.trace.busy_in(s.start, s.end)
    return 100.0 * least / busy if busy > 0 else None
