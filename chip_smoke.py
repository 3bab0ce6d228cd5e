"""Drive the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # the sharded training path

One process, no child processes.  With no option it runs five phases on one
chip, through the library's own entry points, with random weights from
``--seed``:

- ``paper``: Table II (``mkDelayWorker32B``, 60 C, theta_ja 12) through
  ``VS.run`` on the compiled Pallas smoother, against the paper pins and
  against the same solve on the jnp smoother;
- ``control``: the ``diurnal_load_spike`` replay (its RailField is built
  on the chip by one ``solve_batch``);
- ``serving``: qwen3-1.7b at full width, paged ``serve.Engine``, 8 greedy
  requests; first tokens against a plain ``Model.apply`` forward, and the
  paged outputs against the contiguous engine's;
- ``training``: qwen3-1.7b at full width cut to 4 layers, 3 AdamW steps
  through ``repro.launch.train.build``;
- ``abft``: the §V checksummed kernel on one qwen3 MLP matmul, bit for bit
  against its jnp oracle, plus the detect/correct ledger invariants.

``--four-chips`` runs only the FSDP training path and what it is compared
with: 3 steps of the whole 28-layer model through
``repro.launch.train.main`` on a (4, 1) mesh, and one step of a 2-layer cut
on one device and on four, whose loss and grad norm must agree.

Each phase prints one line: its checks, wall and compile seconds (JAX's
compiles and persistent-cache loads, counted by the benchmark's
``benchmarks.chip.harness.CompileClock``), and the device's
``peak_bytes_in_use`` so far.  The last line is the result,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU, or with any phase failing, the script exits non-zero and
prints no result.  The compile cache goes where
``repro.launch.compile_cache.use_compile_cache`` puts it.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.chip.harness import CompileClock  # noqa: E402

ARCH = "qwen3-1.7b"
BF16_ULP = 2.0 ** -7  # spacing of bfloat16 values in [1, 2)


def _peak_bytes(device=None):
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _is_compiled_kernel(fn, *args) -> bool:
    """True when ``fn(*args)`` lowers its ``pallas_call`` to a Mosaic
    custom call (a compiled kernel), not to the interpreter's plain HLO."""
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


# --- phases: each returns (passed, check line) -------------------------------

def phase_paper():
    from repro.core import thermal, voltage_scaling as VS, vtr_benchmarks as vb
    from repro.kernels.thermal_stencil import thermal_stencil

    nl = vb.load("mkDelayWorker32B")
    tc = thermal.ThermalConfig(theta_ja=12.0)
    x = jnp.ones((92, 92), jnp.float32)
    compiled = _is_compiled_kernel(
        lambda t: thermal_stencil(t, t, t, g_lat=0.1, g_v_tamb=0.0, iters=1,
                                  phase=0), x)
    r = VS.run(nl, 60.0, 1.0, tc)
    r_jnp = VS.run(nl, 60.0, 1.0, thermal.ThermalConfig(theta_ja=12.0,
                                                        backend="jnp"))
    t1, tN = r.trace[0], r.trace[-1]
    # the pins of tests/test_voltage_scaling.py::TestTableII, same tolerances
    pins = [
        abs(1000.0 / r.d_worst_ns - 71.6) <= 0.01 * 71.6,
        r.converged and len(r.trace) <= 6,
        abs(t1.v_core - 0.74) <= 0.015,
        abs(t1.power_mw - 485) <= 0.10 * 485,
        abs(t1.t_junct - 65.82) <= 1.0,
        abs(tN.v_core - 0.75) <= 0.015,
        abs(tN.power_mw - 564) <= 0.10 * 564,
        abs(tN.t_junct - 66.77) <= 1.0,
        abs(tN.v_bram - 0.91) <= 0.10,
    ]
    # the smoothers agree to the repo's pallas-vs-jnp steady-state bound
    same_rails = [(a.v_core, a.v_bram) for a in r.trace] == \
        [(a.v_core, a.v_bram) for a in r_jnp.trace]
    dt = max(abs(a.t_junct - b.t_junct) for a, b in zip(r.trace, r_jnp.trace))
    dp = max(abs(a.power_mw - b.power_mw) / b.power_mw
             for a, b in zip(r.trace, r_jnp.trace))
    agree = same_rails and dt <= 1e-3 and dp <= 1e-4
    ok = compiled and all(pins) and agree
    return ok, (
        f"smoother {'compiled Pallas' if compiled else 'NOT compiled'}; "
        f"Table II pins {sum(pins)}/{len(pins)}: {len(r.trace)} iters, "
        f"converged (V_core, V_bram)=({tN.v_core:.2f}, {tN.v_bram:.2f}) "
        f"{tN.power_mw:.1f} mW Tj {tN.t_junct:.2f} C (paper 0.75, 0.91, "
        f"564 mW, 66.77 C); Pallas vs jnp smoother: same rails {same_rails},"
        f" max |dTj| {dt:.2e} C, max rel dP {dp:.2e}")


def phase_control():
    import repro.scenarios as SC

    r = SC.replay(SC.SCENARIOS["diurnal_load_spike"]())
    ok = r.replans == 1 and abs(r.mean_saving - 0.12) <= 0.01
    return ok, (
        f"diurnal_load_spike: {r.replans} replan(s), {r.lut_hits} fast-path "
        f"hits, mean saving {r.mean_saving:.5f} (expect 1 replan, ~0.12); "
        f"fingerprint {r.fingerprint} (CPU: 44a9e7527ffd650d)")


def phase_serving(cfg, seed, n_req=8, prompt_lens=(128, 512), max_new=32,
                  batch_slots=8, max_len=1024, prefill_chunk=256):
    from repro.models.model import Model
    from repro.serve.engine import Engine, Request

    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, n_req)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]

    # reference: one plain forward over the right-padded prompts — causal,
    # so the padding never reaches a prompt's last position
    toks = np.zeros((n_req, prompt_lens[1]), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p

    @jax.jit
    def last_logits(params, tokens, last):
        logits, _ = model.apply(params, {"tokens": tokens})
        return jnp.take_along_axis(
            logits, last[:, None, None], axis=1)[:, 0].astype(jnp.float32)

    ref = np.asarray(last_logits(params, toks, jnp.asarray(lens - 1)))

    outs, finished = {}, {}
    for paged in (True, False):
        eng = Engine(model, params, batch_slots=batch_slots, max_len=max_len,
                     prefill_chunk=prefill_chunk, paged=paged, eos_id=-1)
        for i, p in enumerate(prompts):
            eng.submit(Request(i, p, max_new=max_new))
        eng.run()
        finished[paged] = sum(r.done and r.error is None
                              and len(r.out) == max_new for r in eng.finished)
        outs[paged] = {r.rid: list(r.out) for r in eng.finished}
        del eng
        gc.collect()

    # greedy first token vs the reference argmax; a mismatch passes only as
    # a bf16 near-tie (within 4 bf16 ulps of the reference maximum)
    exact = ties = 0
    for i in range(n_req):
        tok, top = outs[True][i][0], int(np.argmax(ref[i]))
        if tok == top:
            exact += 1
            continue
        gap = ref[i, top] - ref[i, tok]
        ulp = BF16_ULP * 2.0 ** np.floor(np.log2(abs(ref[i, top])))
        ties += bool(gap <= 4 * ulp)
    same = sum(outs[True].get(i) == outs[False].get(i) for i in range(n_req))
    tokens = sum(len(o) for o in outs[True].values())
    ok = (finished[True] == finished[False] == n_req
          and exact + ties == n_req and same == n_req)
    return ok, (
        f"{ARCH} {cfg.num_layers}L paged engine: {finished[True]}/{n_req} "
        f"requests finished, {tokens} tokens; first token == Model.apply "
        f"argmax {exact}/{n_req} (+{ties} bf16 near-ties); paged == "
        f"contiguous {same}/{n_req} (contiguous finished "
        f"{finished[False]}/{n_req})")


def phase_training(cfg, seed, steps=3, batch=8, seq=512):
    from repro.data.pipeline import DataConfig, make_iterator
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build

    _, _, _, train_step, init = build(cfg, make_host_mesh())
    params, opt_state = init(jax.random.PRNGKey(seed))
    # one batch, stepped on repeatedly: at the warmup learning rate (3e-6
    # at step 0) the loss on fresh random-bigram batches does not fall in
    # 3 steps, while the loss on the batch the step descends on must
    data = next(make_iterator(cfg, DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch,
        seed=seed, branch=2)))
    losses = []
    for s in range(steps):
        params, opt_state, m = train_step(params, opt_state, data,
                                          jnp.int32(s))
        losses.append(float(m["loss"]))
    ln_v = math.log(cfg.vocab_size)
    ok = (all(map(math.isfinite, losses)) and abs(losses[0] - ln_v) < 1.0
          and losses[-1] < losses[0])
    return ok, (
        f"{ARCH} width, {cfg.num_layers} layers, one batch {batch}x{seq}: "
        f"losses {', '.join(f'{v:.4f}' for v in losses)} (step 0 vs "
        f"ln V = {ln_v:.4f}; finite, falling)")


def phase_abft(seed, m=256, k=2048, n=6144, p_total=6.4e-5):
    from repro.kernels import ref as kref
    from repro.kernels.abft_matmul import abft_matmul
    from repro.kernels.overscale_matmul import quantize
    from repro.tolerance.abft import AbftMatmul

    rng = np.random.default_rng(seed)
    a = rng.standard_normal((m, k)).astype(np.float32)
    b = (rng.standard_normal((k, n)) / np.sqrt(k)).astype(np.float32)
    probs = np.zeros(32)
    probs[20:] = p_total / 12  # carry-tail bits, as the over-scaled MXU flips
    mm = AbftMatmul(probs, jax.random.PRNGKey(seed), use_pallas=True)

    # the wrapper's first call's kernel inputs, to hold the kernel against
    # its oracle bit for bit
    k1, k2 = jax.random.split(jax.random.fold_in(mm.key, 1))
    qa, _ = quantize(a)
    qb, _ = quantize(b)
    ug = jax.random.bits(k1, (m, n), jnp.uint32)
    ub = jax.random.bits(k2, (m, n), jnp.uint32)
    compiled = _is_compiled_kernel(abft_matmul, qa, qb, ug, ub, mm.cdf)
    got = abft_matmul(qa, qb, ug, ub, mm.cdf)
    want = kref.abft_matmul_ref(qa, qb, ug, ub, mm.cdf)
    bitwise = all(np.array_equal(np.asarray(x), np.asarray(y))
                  for x, y in zip(got, want))

    out = np.asarray(mm(a, b))
    c = mm.counters
    # the ledger invariants of tests/test_tolerance.py (heavy flips)
    ledger = [c.injected > 5, 0 < c.corrected < c.injected,
              c.detected <= c.injected,
              c.escaped == c.injected - c.corrected,
              0.0 < c.escape_rate < c.injected / c.checked]
    rel = float(np.linalg.norm(out - a @ b) / np.linalg.norm(a @ b))
    ok = compiled and bitwise and all(ledger)
    kernel = "compiled" if compiled else "NOT compiled"
    return ok, (
        f"{m}x{k} @ {k}x{n} int8: kernel {kernel}, (c, rowsum, colsum) == "
        f"abft_matmul_ref bitwise "
        f"{bitwise}; ledger invariants {sum(ledger)}/{len(ledger)}: "
        f"injected {c.injected}, detected {c.detected}, corrected "
        f"{c.corrected}, escaped {c.escaped}; output rel err {rel:.4f}")


def phase_fsdp_train(argv):
    from repro.configs import registry
    from repro.launch.train import main as train_main

    losses = train_main(argv)
    peaks = [_peak_bytes(d) for d in jax.devices()]
    ln_v = math.log(registry.get(ARCH).vocab_size)
    spread = None not in peaks and max(peaks) <= 1.25 * min(peaks)
    ok = (all(map(math.isfinite, losses)) and abs(losses[0] - ln_v) < 1.0
          and spread)
    return ok, (
        f"repro.launch.train.main({' '.join(argv)}): losses "
        f"{', '.join(f'{v:.4f}' for v in losses)} (ln V = {ln_v:.4f}); "
        f"peak_bytes_in_use per device {peaks} (spread evenly: {spread})")


def phase_fsdp_vs_single(cfg, seed, batch=8, seq=512, rtol=2 * BF16_ULP):
    from jax.sharding import Mesh

    from repro.data.pipeline import DataConfig, make_iterator
    from repro.launch.train import build

    data = next(make_iterator(cfg, DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch,
        seed=seed, branch=2)))
    res = {}
    for name, devs in (("1 device", jax.devices()[:1]),
                       (f"{len(jax.devices())} devices", jax.devices())):
        mesh = Mesh(np.array(devs).reshape(len(devs), 1), ("data", "model"))
        _, _, _, train_step, init = build(cfg, mesh)
        params, opt_state = init(jax.random.PRNGKey(seed))
        _, _, m = train_step(params, opt_state, data, jnp.int32(0))
        res[name] = (float(m["loss"]), float(m["grad_norm"]))
        del params, opt_state, m
        gc.collect()
    (l1, g1), (l4, g4) = res.values()
    ok = (math.isclose(l1, l4, rel_tol=rtol)
          and math.isclose(g1, g4, rel_tol=rtol))
    return ok, (
        f"{ARCH} width, {cfg.num_layers} layers, one step: "
        + "; ".join(f"{k} loss {v[0]:.5f} grad norm {v[1]:.5f}"
                    for k, v in res.items())
        + f" (agree within rel {rtol:.4f}: {ok})")


# --- driver ----------------------------------------------------------------

def run_phase(name, fn, clock):
    t0 = time.perf_counter()
    try:
        ok, check = fn()
    except Exception as e:  # noqa: BLE001 - report the phase, run the rest
        traceback.print_exc()
        ok, check = False, f"raised {type(e).__name__}: {e}"
    gc.collect()
    ev = clock.counts(since=t0)
    print(f"[{name}] {'PASS' if ok else 'FAIL'} {check} | wall "
          f"{time.perf_counter() - t0:.1f} s, compile "
          f"{ev['compiles'][1]:.1f} s ({ev['compiles'][0]} compiles, "
          f"{ev['cache_loads'][0]} of them cache loads; {ev['traces'][0]} "
          f"traces), peak_bytes_in_use {_peak_bytes()}",
          flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip FSDP training path")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.configs import registry
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    clock = CompileClock()
    cfg = registry.get(ARCH)
    seed = args.seed
    if args.four_chips:
        phases = [
            ("fsdp_train", lambda: phase_fsdp_train(
                ["--arch", ARCH, "--no-smoke", "--steps", "3", "--batch", "8",
                 "--seq", "512", "--log-every", "1"])),
            ("fsdp_vs_single", lambda: phase_fsdp_vs_single(
                cfg.replace(num_layers=2), seed)),
        ]
    else:
        phases = [
            ("paper", phase_paper),
            ("control", phase_control),
            ("serving", lambda: phase_serving(cfg, seed)),
            ("training", lambda: phase_training(
                cfg.replace(num_layers=4), seed)),
            ("abft", lambda: phase_abft(seed)),
        ]
    results = [run_phase(name, fn, clock) for name, fn in phases]
    if not all(results):
        return 1
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
