"""Whole served step: forward FLOPs of every token fed in the window (prompt
tokens and decode inputs, each over its real context;
``counts.serve_flops``), over the window and the chip's bf16 peak, in %."""
from benchmarks.chip import counts


def read(run):
    flops = sum(counts.serve_flops(run.src, p, n)
                for t in run.ticks if t.end <= run.t1 for p, n in t.fed)
    if flops <= 0:
        return None
    return 100.0 * flops / ((run.t1 - run.t0) * run.peak["bf16_flops_per_s"])
