"""Find a serving cell's knee: the highest open-loop rate it sustains.

    python3 -m benchmarks.chip.sweep --workload <cell> --seed <n> \\
        --seconds <s> --rates 1.5,2,3,...

One process, one chip: for each rate a fresh engine (its programs come
from the compile cache) serves the cell's mix at that rate for
``--seconds``; one line per rate gives the completed output tokens/s
against the offered, the TTFT percentiles, the backlog at the close and the
mean host-clock tick time by kind.  The cells' rates are fixed from this
once; the benchmark's own runs never search for one.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time

import numpy as np

from benchmarks.chip import harness, traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell, devs = harness.open_cell(args.workload)
    drv = cell.driver()
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell.mix)
        mix["arrivals"]["rate_per_s"] = rate
        cell_r = copy.copy(cell)
        cell_r.mix = mix
        ctx = harness.Context(cell_r, args.seed + i, args.seconds, False,
                              devs, time.perf_counter(),
                              harness.CompileClock())
        items = traffic.generate(mix, ctx.seed, ctx.seconds,
                                 cell.config["vocab_size"])
        ref, specs, model, eng = drv.build(ctx, cell.config, mix)
        drv.warm(eng, items, cell.config["vocab_size"])
        recs, ticks, t0, t1, _ = drv.drive(ctx, eng, items)
        e2e, n = drv.end_to_end(recs, ticks, t0, t1, 0.0)
        offered = sum(it.max_new for it in items) / args.seconds
        pf = [t.end - t.start for t in ticks if t.prefill]
        dc = [t.end - t.start for t in ticks if not t.prefill]
        ttft = sorted(((r.tokens[0] if r.tokens else t1) - r.due) * 1e3
                      for r in recs)
        print(json.dumps({
            "rate": rate, "offered_tokens_per_s": offered,
            "output_tokens_per_s": e2e["output_tokens_per_s"][0],
            "ttft_p50_ms": float(np.percentile(ttft, 50)),
            "ttft_p90_ms": e2e["ttft_p90_ms"][0],
            "itl_p95_ms": e2e["itl_p95_ms"][0],
            "queued_at_close": sum(1 for r in recs
                                   if r.admitted is None),
            "active_at_close": sum(1 for r in recs if r.admitted is not None
                                   and not r.req.done),
            "unstarted_due": sum(1 for r in recs if not r.tokens),
            "prefill_ticks": len(pf), "decode_ticks": len(dc),
            "prefill_tick_ms": 1e3 * float(np.mean(pf)) if pf else None,
            "decode_tick_ms": 1e3 * float(np.mean(dc)) if dc else None,
            "mean_active": float(np.mean([len(t.contexts) for t in ticks
                                          if not t.prefill] or [0])),
            "n": n}), flush=True)
        del eng, model
        drv.free_device()
    return 0


if __name__ == "__main__":
    sys.exit(main())
