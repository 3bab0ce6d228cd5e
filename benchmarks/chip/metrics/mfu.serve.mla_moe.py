"""Whole served step of a latent-attention expert model: forward FLOPs of
every token fed in the window, each over its real context
(``counts_mla_moe.serve_flops``), plus the routed experts' FLOPs for the
picks that land on experts this chip holds, over the window and the chip's
bf16 peak, in %.  The held picks per token fed are the traced ticks' (the
engine's ``routed_local`` on each commit span, over the tokens the tick
fed); nothing where the commit spans carry no expert counters."""
from benchmarks.chip import counts_mla_moe as C


def read(run):
    routed = fed = 0
    for s, t in run.traced_ticks():
        counters = C.expert_counters(run, s)
        if counters is not None:
            routed += counters[0]
            fed += sum(n for _, n in t.fed)
    if not fed:
        return None
    ticks = [t for t in run.ticks if t.end <= run.t1]
    tokens = sum(n for t in ticks for _, n in t.fed)
    flops = (sum(C.serve_flops(run.src, p, n) for t in ticks for p, n in t.fed)
             + C.expert_flops(run.src, tokens * routed / fed))
    return 100.0 * flops / ((run.t1 - run.t0) * run.peak["bf16_flops_per_s"])
