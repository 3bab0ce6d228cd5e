"""Readings that a serving cell's check limit is set from, in one process.

    python3 -m benchmarks.chip.calibrate --workload <cell> --seeds 1,2,... \\
        --seconds <s>

For each seed: weights, traffic and an engine built from that seed as a
run builds them (programs from the compile cache after the first seed), a
short window of the cell's own mix, the program freed, then, over the
sample the benchmark's check takes, the widest gap of the served tokens
below the reference's best logit (the program's reading) and the widest gap
of the tokens that the fp8 control ranks first (the control's reading).
The limit goes between the largest program reading and the smallest
control reading; the benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from benchmarks.chip import compare, harness, traffic, weights


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell, devs = harness.open_cell(args.workload)
    drv, cfg, mix = cell.driver(), cell.config, cell.mix
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ctx = harness.Context(cell, seed, args.seconds, False, devs, t,
                              harness.CompileClock())
        items = traffic.generate(mix, seed, args.seconds, cfg["vocab_size"])
        ref, specs, model, eng = drv.build(ctx, cfg, mix)
        drv.warm(eng, items, cfg["vocab_size"])
        recs, ticks, t0, t1, _ = drv.drive(ctx, eng, items)
        finished = drv.served(recs, t1)
        sample = compare.pick(finished, seed, mix["check"]["tokens"])
        del eng, model
        drv.free_device()
        w = weights.canonical(specs, seed, cfg["serve_dtype"], "float32")
        L = mix["engine"]["max_len"]
        prog = compare.max_served_gap(ref, w, cfg, sample, L)
        ctrl = compare.max_served_gap(ref, w, cfg, sample, L, mm=ref.fp8)
        del w
        gc.collect()
        print(json.dumps({"seed": seed, "program_gap": prog,
                          "control_fp8_gap": ctrl, "requests": len(sample),
                          "tokens": sum(len(o) for _, o in sample),
                          "finished": len(finished), "due": len(recs),
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
