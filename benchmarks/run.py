"""Benchmark harness master: one entry per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV per the repo convention and writes
the full structured results to artifacts/bench_results.json.  Exits
non-zero when any entry raised (every entry still runs and is reported).

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import kernels_bench, paper_figs, roofline  # noqa: E402

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args, _ = ap.parse_known_args()
    quick = args.quick

    benches = [
        ("fig2_characterization", paper_figs.fig2_characterization),
        ("fig3_activity", paper_figs.fig3_activity),
        ("table2_casestudy", paper_figs.table2_casestudy),
        ("fig6_power", paper_figs.fig6_power),
        ("fig7_energy", paper_figs.fig7_energy),
        ("fig8_overscaling", paper_figs.fig8_overscaling),
        ("tpu_runtime", paper_figs.tpu_runtime_bench),
        ("dynamic_lut", paper_figs.dynamic_lut_bench),
        ("kernels", kernels_bench.run),
        ("roofline", roofline.run),
    ]
    os.makedirs(ART, exist_ok=True)
    results, failed = {}, []
    print("name,us_per_call,derived")
    for name, fn in benches:
        t0 = time.time()
        try:
            res = fn(quick=quick)
            ok = True
        except Exception as e:  # noqa: BLE001 - report, run the rest, fail
            traceback.print_exc()
            res = {"error": f"{type(e).__name__}: {e}"}
            ok = False
            failed.append(name)
        us = (time.time() - t0) * 1e6
        results[name] = res
        derived = _headline(name, res) if ok else res["error"]
        print(f"{name},{us:.0f},{derived}")

    with open(os.path.join(ART, "bench_results.json"), "w") as f:
        json.dump(results, f, indent=1, default=float)
    print(f"# wrote {os.path.join(ART, 'bench_results.json')}")
    if failed:
        print(f"# FAILED: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


def _headline(name: str, res) -> str:
    try:
        if name == "fig2_characterization":
            return (f"sb40C={res['sb_delay_40C_over_100C']:.3f}(0.85) "
                    f"sbP={res['sb_power_ratio_0.68V']:.2f}(0.68)")
        if name == "fig3_activity":
            return f"a_int(1.0)={res['alpha_internal'][-1]}(0.27)"
        if name == "table2_casestudy":
            f_ = res["iters"][-1]
            return (f"final=({f_['v_core']:.2f},{f_['v_bram']:.2f})"
                    f"{f_['power_mw']}mW(paper (0.75,0.91)564mW)")
        if name == "fig6_power":
            return (f"avg40C={res['avg_saving_40C_alpha1']*100:.1f}%"
                    f"(28.3-36.0) avg65C={res['avg_saving_65C_alpha1']*100:.1f}%"
                    f"(20.0-25.0)")
        if name == "fig7_energy":
            return (f"avg={res['avg_saving']*100:.1f}%(44-66) "
                    f"freq_ratio={res['avg_freq_ratio']:.2f}(0.37)")
        if name == "fig8_overscaling":
            l135 = [r for r in res["lenet"] if r["gamma"] == 1.35]
            h135 = [r for r in res["hd"] if r["gamma"] == 1.35]
            if l135 and h135:
                return (f"g1.35: lenet {l135[0]['saving']*100:.0f}%/"
                        f"acc{l135[0]['acc']:.3f} hd {h135[0]['saving']*100:.0f}%/"
                        f"acc{h135[0]['acc']:.3f} (paper 48%/-3% 50%/-0.5%)")
            return "ok"
        if name == "tpu_runtime":
            t = res["train_compute_bound"]
            return (f"train: save={t['power_save']['saving']*100:.1f}% "
                    f"minE={t['min_energy']['saving']*100:.1f}%")
        if name == "dynamic_lut":
            return (f"match={res['match']} batch={res['wall_batch_s']}s "
                    f"seq-run={res['wall_sequential_run_s']}s "
                    f"(seed impl {res['seed_implementation_s']}s)")
        if name == "kernels":
            return f"{len(res)} timings"
        if name == "roofline":
            n = len(res["cells"])
            doms = [c["dominant"] for c in res["cells"]]
            return (f"{n} cells: {doms.count('compute')}comp/"
                    f"{doms.count('memory')}mem/{doms.count('collective')}coll")
    except Exception as e:  # noqa
        return f"headline-error {e}"
    return "ok"


if __name__ == "__main__":
    sys.exit(main())
