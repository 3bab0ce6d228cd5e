"""Run one benchmark cell on the chip(s) and print its result line.

    python3 -m benchmarks.chip.run --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

One process holds the chip(s): it finds the cell's configuration, traffic
and metric files by the names in ``BENCHMARK.json``, builds and warms up
(set-up), measures for ``--seconds``, checks what the timed path produced
against the plain reference, and prints one JSON line last.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402

from benchmarks.chip import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, devs = harness.open_cell(args.workload)
    ctx = harness.Context(cell, args.seed, args.seconds, bool(args.trace),
                          devs, T_START, harness.CompileClock())
    result, checks = cell.driver().run(ctx)
    harness.finish(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
