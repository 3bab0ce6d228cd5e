"""Serving cells whose float32 weights do not fit on the chip and whose
widest gap is set by the gate: ``drivers/serve.py``'s run, with two
departures in its check.

- The reference's weights are held in the served dtype, and the reference
  upcasts one layer's at a time (DeepSeek-V2's 9 layers are 9.7 GB in
  bfloat16, 19.4 GB in float32).  The values compared are the same: a
  served weight upcast is exact.
- ``correct`` also holds the p90 of the served tokens' gaps to the
  configuration's ``limits.p90_logit_gap``.  In bfloat16 the gate picks
  other experts than the float32 reference on a few positions, and a
  flipped expert, weighted x16, sets the widest gap; a lower precision
  moves every position.  So the p90 parts the program from the fp8
  control, and ``limits.max_logit_gap`` stays a guard against wrong
  tokens, well above the program's widest gap.

    python3 -m benchmarks.chip.drivers.serve_upcast --workload <cell> \\
        --seeds 1,2,... --seconds <s>

makes ``calibrate.py``'s readings for such a cell (the program's gaps, the
fp8 control's and those of tokens drawn at random, each judged by the
configuration's limits), and counts on the longest checked sequence the
expert-layer routing decisions in which the program's gate and the
reference's differ (:func:`program_routes`).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import types

import numpy as np

from benchmarks.chip import compare, harness, traffic, weights


def _served(specs, seed, dtype, out_dtype=None):
    """``weights.canonical`` held in the served dtype.  The check asks for
    float32, which the reference makes of each layer as it runs it."""
    assert out_dtype in (None, "float32"), out_dtype
    return weights.canonical(specs, seed, dtype)


class _Gaps:
    """``compare`` as the serving driver's check calls it, keeping every
    served token's gap of the last call."""
    pick = staticmethod(compare.pick)

    def __init__(self):
        self.gaps = np.zeros(0)

    def max_served_gap(self, ref, w, src, sample, length, mm=None):
        assert mm is None, "the run's check reads the program alone"
        self.gaps = np.concatenate(
            [compare.served_gaps(ref, w, src, p, o, length) for p, o in sample]
            or [np.zeros(0)])
        return float(self.gaps.max()) if len(self.gaps) else None


# a copy of the serving driver of its own, whose check draws and reads
# through these two
_gaps = _Gaps()
_serve = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve.py"),
    "bench_driver_serve_for_upcast")
_serve.weights = types.SimpleNamespace(program_tree=weights.program_tree,
                                       canonical=_served)
_serve.compare = _gaps
build, warm, drive, served = _serve.build, _serve.warm, _serve.drive, \
    _serve.served
free_device = _serve.free_device


def p90(gaps) -> float | None:
    """The 90th percentile of per-token gaps (None without any)."""
    return float(np.percentile(gaps, 90)) if len(gaps) else None


def within(gaps, limits: dict) -> bool:
    """Whether per-token gaps pass both of the configuration's limits."""
    return (len(gaps) > 0 and float(np.max(gaps)) <= limits["max_logit_gap"]
            and p90(gaps) <= limits["p90_logit_gap"])


def run(ctx):
    _gaps.gaps = np.zeros(0)
    result, checks = _serve.run(ctx)
    limit = ctx.cell.config["limits"]["p90_logit_gap"]
    q = p90(_gaps.gaps)
    result["correct"] = bool(result["correct"] and q is not None
                             and q <= limit)
    return result, {**checks, "p90_logit_gap": (q, limit)}


def program_routes(model, params, tokens, chunk: int = 256) -> np.ndarray:
    """The program's gate on a sequence fed through its decode path in
    chunks, as the engine feeds a prompt: (expert layers, S, k) picks,
    sorted per token."""
    import jax
    import jax.numpy as jnp
    from repro.models import layers as L
    from repro.models import moe
    from repro.models import transformer as tf
    cfg, plan = model.cfg, model.plan
    n = len(tokens)
    T = -(-n // chunk) * chunk
    toks = np.zeros(T, np.int32)
    toks[:n] = tokens

    @jax.jit
    def step(params, cache, x_tok, pos):
        x = L.embed_apply(params["embed"], x_tok[None], cfg, plan)
        for i in range(cfg.first_k_dense):
            x, cache[f"dense{i}"], _ = tf.attn_block_decode(
                params["blocks"][f"dense{i}"], x, cache[f"dense{i}"], pos,
                cfg, plan)

        # the expert layers as lm_decode runs them: every layer's experts
        # whole, with this layer's index
        stack, experts = tf._split_experts(params["blocks"]["stack"])
        layers = jnp.arange(jax.tree_util.tree_leaves(stack)[0].shape[0])

        def body(x, pcl):  # attn_block_decode, keeping the gate's picks
            p, c, layer = pcl
            a, c = tf.attn.mla_decode(p["attn"], L.norm_apply(p["ln1"], x, cfg),
                                      c, pos, cfg, plan)
            x = x + a
            h = L.norm_apply(p["ln2"], x, cfg)
            _, idx, _, _ = moe.router_topk(
                moe._router_logits(p["moe"], h[0]), cfg.num_experts_per_tok,
                **moe.gate_args(cfg))
            m, _ = moe.moe_dropless({**p["moe"], **experts}, h, cfg, plan,
                                    layer)
            return x + m, (c, idx)

        _, (new_stack, idx) = jax.lax.scan(
            body, x, (stack, cache["stack"], layers))
        return {**cache, "stack": new_stack}, idx

    cache, picks = model.cache(1, T), []
    for s in range(0, T, chunk):
        cache, idx = step(params, cache, jnp.asarray(toks[s:s + chunk]), s)
        picks.append(np.asarray(idx))
    return np.sort(np.concatenate(picks, axis=1)[:, :n], -1)


def held_differs(cfg, got, want) -> np.ndarray:
    """(layers, S): whether two routings pick different held experts."""
    lo, hi = cfg.held_expert_start, cfg.held_expert_start + cfg.n_held
    held = lambda a: frozenset(int(e) for e in a if lo <= e < hi)
    return np.array([[held(g) != held(w) for g, w in zip(gl, wl)]
                     for gl, wl in zip(got, want)])


def routing_differences(cfg, got, want) -> dict:
    """Decisions (expert layer x token) whose picks differ between two
    routings, those whose held picks differ (what changes this chip's
    output), and the first position where a held pick differs."""
    on_held = held_differs(cfg, got, want)
    first = np.flatnonzero(on_held.any(0))
    return {"decisions": int(got.shape[0] * got.shape[1]),
            "differ": int(np.any(got != want, -1).sum()),
            "differ_on_held": int(on_held.sum()),
            "first_held_position": int(first[0]) if len(first) else None}


def calibrate(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell, devs = harness.open_cell(args.workload)
    cfg, mix = cell.config, cell.mix
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ctx = harness.Context(cell, seed, args.seconds, False, devs, t,
                              harness.CompileClock())
        items = traffic.generate(mix, seed, args.seconds, cfg["vocab_size"])
        ref, specs, model, eng = build(ctx, cfg, mix)
        warm(eng, items, cfg["vocab_size"])
        recs, ticks, t0, t1, _ = drive(ctx, eng, items)
        sample = compare.pick(served(recs, t1), seed, mix["check"]["tokens"])
        if not sample:
            print(json.dumps({"seed": seed, "finished": 0}), flush=True)
            continue
        prompt, out = sample[0]
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        got = program_routes(model, eng.params, seq)
        del eng, model
        free_device()
        w = _served(specs, seed, cfg["serve_dtype"])
        L = mix["engine"]["max_len"]
        # a token drawn at random in place of each served one: what a
        # program that serves wrong tokens reads
        rng = np.random.default_rng(seed)
        drawn = [(p, rng.integers(0, cfg["vocab_size"], len(o)))
                 for p, o in sample]
        gaps = {name: np.concatenate([compare.served_gaps(ref, w, cfg, p, o,
                                                          L, mm)
                                      for p, o in seqs])
                for name, seqs, mm in (("program", sample, None),
                                       ("control_fp8", sample, ref.fp8),
                                       ("random_tokens", drawn, None))}
        padded = np.zeros(L, np.int32)
        padded[:len(seq)] = seq
        want = np.sort(np.asarray(ref.routes(w, padded, cfg))[:, :len(seq)],
                       -1)
        del w
        gc.collect()
        q = (50, 90, 99, 100)  # quantiles of the per-token gaps
        # the first served position (in the sequence) whose gap passes 0.4
        wide = np.flatnonzero(gaps["program"][:len(out)] > 0.4)
        print(json.dumps({"seed": seed,
                          **{f"{k}_gap_q50_90_99_100":
                             np.percentile(g, q).tolist()
                             for k, g in gaps.items()},
                          "within_limits": {k: within(g, cfg["limits"])
                                            for k, g in gaps.items()},
                          "first_wide_gap_position":
                              int(len(prompt) - 1 + wide[0]) if len(wide)
                              else None,
                          "requests": len(sample),
                          "tokens": sum(len(o) for _, o in sample),
                          "routing": routing_differences(
                              harness.program_config(cfg), got, want),
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(calibrate())
