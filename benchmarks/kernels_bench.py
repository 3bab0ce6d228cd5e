"""Kernel microbenchmarks (interpret-mode wall times are STRUCTURAL only —
the CPU interpreter executes the kernel body; TPU perf comes from the
roofline, not these numbers). Also times each kernel's jnp reference, which
IS meaningful on CPU.

The ``thermal_solve_*_us`` family times the full steady-state solve at the
paper's 92x92 / theta_ja=12 reference point through each solver tier
(multigrid cold + warm restart, chunked Jacobi, seed Jacobi) — the number
every fixed point in the repo bottoms out in.

``--smoke`` additionally runs the closed-loop serving tick benchmark
(repro.control): engine tokens/s, LUT-fast-path control tick latency, and
full-solver replan latency. ``--json PATH`` dumps every number for the CI
artifact. ``--check BASELINE.json`` compares against a committed baseline
(BENCH_kernels.json) and fails on >2x regression of any jnp-path ``*_us``
entry (interpret-mode entries are structural and excluded)."""
from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, iters=3) -> float:
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6  # us


def run(quick: bool = False) -> Dict:
    from repro.kernels import ops, ref as kref
    from repro.kernels.flash_attention import flash_attention
    key = jax.random.PRNGKey(0)
    out = {}

    S, D = (256, 64) if quick else (1024, 128)
    q = jax.random.normal(jax.random.fold_in(key, 1), (S, D))
    k = jax.random.normal(jax.random.fold_in(key, 2), (S, D))
    v = jax.random.normal(jax.random.fold_in(key, 3), (S, D))
    out["flash_attention_ref_us"] = _time(
        lambda a, b, c: kref.flash_attention_ref(a, b, c), q, k, v)
    out["flash_attention_interpret_us"] = _time(
        lambda a, b, c: flash_attention(a, b, c, interpret=True), q, k, v)

    # paged-attention decode: block-table K/V gather through scalar
    # prefetch (B slots, non-contiguous pages, one query token per slot)
    Bp, Hp, Hkv, Dp = (4, 8, 2, 64)
    ps, npages = (16, 4) if quick else (16, 16)
    Pp = Bp * npages
    qp = jax.random.normal(jax.random.fold_in(key, 20), (Bp, Hp, Dp))
    kp = jax.random.normal(jax.random.fold_in(key, 21), (Pp + 1, ps, Hkv, Dp))
    vp = jax.random.normal(jax.random.fold_in(key, 22), (Pp + 1, ps, Hkv, Dp))
    posp = jnp.full((Bp,), npages * ps - 1, jnp.int32)
    # page slot*npages + j carries logical positions [j*ps, (j+1)*ps); the
    # trailing pool index Pp is the invalid null page (ids = -1)
    idsp = (jnp.arange(ps, dtype=jnp.int32)[None]
            + (jnp.arange(Pp + 1, dtype=jnp.int32)[:, None] % npages) * ps
            ).at[Pp].set(-1)
    btp = (jnp.arange(npages, dtype=jnp.int32)[None]
           + jnp.arange(Bp, dtype=jnp.int32)[:, None] * npages)
    out["paged_attention_ref_us"] = _time(
        lambda *a: kref.paged_attention_ref(*a), qp, kp, vp, idsp, btp, posp)
    out["paged_attention_interpret_us"] = _time(
        lambda *a: ops.paged_attention_decode(*a), qp, kp, vp, idsp, btp,
        posp)

    b, S2, H, P, N = 1, (128 if quick else 512), 8, 32, 64
    xh = jax.random.normal(jax.random.fold_in(key, 4), (b, S2, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 5),
                                           (b, S2, H)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 6), (H,)) * 0.3)
    B = jax.random.normal(jax.random.fold_in(key, 7), (b, S2, H, N)) * 0.3
    Cm = jax.random.normal(jax.random.fold_in(key, 8), (b, S2, H, N)) * 0.3
    out["mamba_scan_ref_us"] = _time(
        lambda *a: kref.mamba_scan_ref(*a, 64)[0], xh, dt, A, B, Cm)
    out["mamba_scan_interpret_us"] = _time(
        lambda *a: ops.mamba_scan_b(*a, chunk=64), xh, dt, A, B, Cm)

    m = 92
    from repro.core import thermal
    from repro.core.thermal import ThermalConfig, conductances
    tc = ThermalConfig(theta_ja=12.0)
    g_v, g_lat = conductances(m, m, tc)
    T = jnp.full((m, m), 30.0)
    Pw = jax.random.uniform(jax.random.fold_in(key, 9), (m, m)) * 5e-3
    nbrc = jnp.full((m, m), 4.0).at[0, :].add(-1).at[-1, :].add(-1) \
        .at[:, 0].add(-1).at[:, -1].add(-1)
    diag = g_v + g_lat * nbrc
    out["thermal_stencil_ref_us"] = _time(
        lambda *a: kref.thermal_stencil_ref(*a, 64), T, Pw, diag, g_lat,
        g_v * 25.0)
    out["thermal_stencil_interpret_us"] = _time(
        lambda t, p, d: ops.thermal_sweep(t, p, d, g_lat=g_lat,
                                          g_v_tamb=g_v * 25.0, iters=64),
        T, Pw, diag)

    # full steady-state solve, 92x92 theta_ja=12 (the paper's Table-II die):
    # multigrid tier (cold + warm restart) vs the chunked and seed (one
    # reduce per sweep) Jacobi relaxations — all pure-jnp on CPU
    P_mw = Pw.reshape(-1) * 1e3
    tc_seed = ThermalConfig(theta_ja=12.0, solver="jacobi", check_every=1)
    tc_chunk = ThermalConfig(theta_ja=12.0, solver="jacobi")
    out["thermal_solve_multigrid_us"] = _time(
        lambda p: thermal.solve(p, m, m, 25.0, tc), P_mw)
    T_conv = thermal.solve(P_mw, m, m, 25.0, tc)
    out["thermal_solve_multigrid_warm_us"] = _time(
        lambda p, t0: thermal.solve(p, m, m, 25.0, tc, t0), P_mw, T_conv)
    out["thermal_solve_jacobi_chunked_us"] = _time(
        lambda p: thermal.solve(p, m, m, 25.0, tc_chunk), P_mw)
    out["thermal_solve_jacobi_seed_us"] = _time(
        lambda p: thermal.solve(p, m, m, 25.0, tc_seed), P_mw)
    out["thermal_solve_speedup"] = (out["thermal_solve_jacobi_seed_us"]
                                    / out["thermal_solve_multigrid_us"])

    M = 128 if quick else 256
    a8 = jax.random.randint(jax.random.fold_in(key, 10), (M, M), -128, 127,
                            jnp.int8)
    b8 = jax.random.randint(jax.random.fold_in(key, 11), (M, M), -128, 127,
                            jnp.int8)
    ug = jax.random.bits(jax.random.fold_in(key, 12), (M, M), jnp.uint32)
    ub = jax.random.bits(jax.random.fold_in(key, 13), (M, M), jnp.uint32)
    from repro.kernels.overscale_matmul import bit_probs_to_cdf
    probs = np.zeros(32)
    probs[28:] = 0.01
    cdf = bit_probs_to_cdf(probs)
    out["overscale_matmul_ref_us"] = _time(
        kref.overscale_matmul_ref, a8, b8, ug, ub, cdf)
    out["overscale_matmul_interpret_us"] = _time(
        lambda *a: ops.overscale_mm(*a), a8, b8, ug, ub, cdf)

    # ABFT-checksummed variant (repro.tolerance): the jnp oracle is the
    # gated timing; the fused Pallas kernel is structural on CPU.  The
    # detect rate is data (deterministic given the key), not a gate.
    out["abft_matmul_us"] = _time(
        kref.abft_matmul_ref, a8, b8, ug, ub, cdf)
    out["abft_matmul_interpret_us"] = _time(
        lambda *a: ops.abft_mm(*a), a8, b8, ug, ub, cdf)
    from repro.tolerance import AbftMatmul
    sparse = np.zeros(32)
    sparse[20:] = 0.002 / 12  # distinct deltas: syndromes localize
    af = jax.random.normal(jax.random.fold_in(key, 14), (M, M))
    bf = jax.random.normal(jax.random.fold_in(key, 15), (M, M))
    mm = AbftMatmul(sparse, jax.random.fold_in(key, 16))
    mm(af, bf)
    assert mm.counters.injected > 0
    out["sdc_detect_rate"] = mm.counters.detect_rate
    return out


def closed_loop(quick: bool = True) -> Dict:
    """Closed-loop serving tick benchmark (DESIGN.md §3).

    Measures the latencies that matter for the control plane under load:
    serve-engine token throughput, the LutController fast-path tick
    (interpolated lookup + actuation + thermal settle), a full-solver
    replan (warm jit), the thermal-aware admission decision, and the
    tokens/joule the §8 acceptance day serves at."""
    import jax
    import numpy as np

    from repro import control as ctl
    from repro.configs import registry
    from repro.core import runtime as RT
    from repro.core import tpu_fleet as TF
    from repro.models.model import Model
    from repro.serve.engine import Engine, Request

    out = {}

    # -- serving throughput under continuous batching ------------------------
    # the headline number runs the PAGED path with speculative decode (the
    # production configuration); the contiguous engine rides along as the
    # decode-tax comparator.  Best-of-3 days: the tokens are deterministic,
    # only the wall clock varies.
    cfg = registry.get("llama3.2-1b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_req = 6 if quick else 16

    def _serve_day(eng):
        best = 0.0
        for _ in range(3):
            for rid in range(n_req):
                eng.submit(Request(rid, np.arange(4 + rid % 3)
                                   % cfg.vocab_size, max_new=8))
            eng.step()  # prefill/decode compiles land on day one only
            t0 = time.time()
            eng.run()
            toks = sum(len(r.out) for r in eng.finished)
            eng.finished.clear()
            best = max(best, toks / (time.time() - t0))
        return best

    eng = Engine(model, params, batch_slots=4, max_len=64, paged=True,
                 speculate=3)
    out["serve_tokens_per_s"] = _serve_day(eng)
    out["spec_decode_accept_rate"] = eng.spec_accept_rate
    assert out["spec_decode_accept_rate"] > 0.0
    eng_c = Engine(model, params, batch_slots=4, max_len=64)
    out["serve_tokens_per_s_contiguous"] = _serve_day(eng_c)

    # -- control-plane latencies --------------------------------------------
    from repro.control.lut import sweep_points
    prof = TF.StepProfile.from_roofline(compute_s=0.7, memory_s=0.4,
                                        collective_s=0.15)
    rt = RT.EnergyAwareRuntime(prof, policy="power_save")
    t_knots, u_knots = sweep_points(15.0, 40.0, 6), sweep_points(0.25, 1.0, 4)
    t0 = time.time()
    controller = rt.controller(sweep=(15.0, 40.0, 6),
                               util_sweep=(0.25, 1.0, 4), guard_band_c=3.0)
    out["lut_build_s"] = time.time() - t0  # cold 2-D field incl. compiles

    # warm 2-D RailField rebuild (the steady-state refresh cost): the whole
    # ambient x utilization grid through the early-freeze batched solver,
    # vs the lockstep path.  Best-of-3 so one GC pause / device-sync
    # hiccup can't trip the 2x gate; the speedup ratio is REPORTED data,
    # not a gated claim — at this 6x4 grid on CPU the compaction win and
    # the segment-dispatch overhead roughly cancel (the win grows with
    # batch size and convergence spread; the build stays ONE logical
    # sweep either way)
    def _best_of(fn, n=3):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return min(ts) * 1e3

    field = rt.planner.rail_field(t_knots, u_knots)  # warm the jits
    out["railfield_build_ms"] = _best_of(
        lambda: rt.planner.rail_field(t_knots, u_knots))
    rt.planner.rail_field(t_knots, u_knots, early_freeze=False)  # compile
    out["railfield_build_lockstep_ms"] = _best_of(
        lambda: rt.planner.rail_field(t_knots, u_knots,
                                      early_freeze=False))
    out["railfield_build_speedup"] = (out["railfield_build_lockstep_ms"]
                                      / out["railfield_build_ms"])
    iters = 2000  # per-chip bilinear fast-path lookup
    t0 = time.perf_counter()
    for k in range(iters):
        field.lookup(27.3 + 1e-4 * k, 0.77)
    out["railfield_lookup_us"] = (time.perf_counter() - t0) / iters * 1e6

    amb = ctl.AmbientSensor(25.0)
    fleet = ctl.FleetActuator.from_runtime(rt)
    loop = ctl.ControlLoop(ctl.TelemetryBus([amb, fleet]), controller,
                           [fleet])
    loop.step(now=0.0)  # cold start: solver replan + jit compile

    amb.trace = 35.0  # beyond the guard band -> warm full-solver replan
    t0 = time.perf_counter()
    loop.step(now=1.0)
    out["replan_latency_ms"] = (time.perf_counter() - t0) * 1e3

    iters = 5
    t0 = time.perf_counter()
    for k in range(iters):  # quasi-static drift stays on the LUT fast path
        amb.trace = 35.0 + 0.1 * (k + 1)
        loop.step(now=2.0 + k)
    out["ctl_tick_ms"] = (time.perf_counter() - t0) / iters * 1e3
    assert controller.stats.replans == 2 and controller.stats.lut_hits == iters

    # the replan core in isolation (warm jit, warm-started fixed point,
    # averaged — replan_latency_ms above is one tick incl. settle/telemetry
    # and is noise-dominated): Algorithm 1 rails -> thermal solve -> repeat
    rt.plan()
    t0 = time.perf_counter()
    for _ in range(5):
        rt.plan()
    out["fleet_plan_ms"] = (time.perf_counter() - t0) / 5 * 1e3

    # -- thermal-aware admission (DESIGN.md §8) ------------------------------
    # decision latency: one AdmissionController tick = marginal-power
    # pricing off the p_nom grid + the inner RailField lookup (the path a
    # production scheduler runs per control tick, gated like the lookup)
    from repro import scenarios as sc
    from repro.control.admission import AdmissionController
    adm = AdmissionController(controller, defer_premium=1.05)
    adm.decide(ctl.Snapshot(t_amb=25.0, queued=3, active=1, slots=4))
    iters = 1000
    t0 = time.perf_counter()
    for k in range(iters):
        adm.decide(ctl.Snapshot(t_amb=25.0 + 1e-4 * k, queued=3, active=1,
                                slots=4))
    out["admission_latency_us"] = (time.perf_counter() - t0) / iters * 1e6

    # served efficiency on the §8 acceptance day (hot window -> cool-down,
    # burst during the hot window): tokens per joule with thermal-aware
    # admission.  Deterministic inputs, but wall-clock-free only in the
    # token ledger — the energy integral is simulated, so the number is
    # stable; it is still reported (not gated) because it shifts whenever
    # the power model or the day is retuned.
    day = sc.serve_day(ticks=8, hot=38.0, cool=16.0, cool_at=4)
    wl = sc.poisson_burst(burst_at=1, burst_n=6, seed=0)
    rep = sc.serve_replay(day, wl, model, params, controller=adm,
                          runtime=rt, engine_steps=6, batch_slots=4,
                          max_len=64)
    out["serve_tokens_per_joule"] = rep.tokens_per_joule

    # -- fault containment (DESIGN.md §9) ------------------------------------
    # thermal-emergency preemption latency on the PAGED path: one Preempt
    # actuation = gather the victim's allocated block-table pages (page-
    # exact, not the slot's full span), device->host into the page pool,
    # free the pages, requeue (the resume tick afterwards is untimed)
    eng2 = Engine(model, params, batch_slots=4, max_len=64, paged=True)
    for rid in range(10):
        eng2.submit(Request(rid, np.arange(6) % cfg.vocab_size, max_new=48))
    eng2.step()  # fill slots, pay prefill/decode + gather compiles
    eng2.preempt_to(eng2.B - 1)  # compile the row gather outside the timing
    lat = []
    for _ in range(3 if quick else 8):
        while sum(r is not None for r in eng2.slot_req) < 2 and eng2.step():
            pass
        t0 = time.perf_counter()
        eng2.preempt_to(1)
        lat.append(time.perf_counter() - t0)
        eng2.step()  # untimed: re-admit (bitwise resume) for the next round
    out["preempt_latency_us"] = float(np.mean(lat)) * 1e6

    # watchdog recovery on the §9 chaos day: ticks from a trip (missed
    # deadline / diverged solver) back to the normal solver-eligible path.
    # Deterministic (seeded fault streams), so the --check gate pins it.
    crep = sc.replay(sc.chaos_day(ticks=20), runtime=rt,
                     controller=controller)
    assert crep.recover_ticks, "chaos day completed no watchdog episode"
    out["mean_ticks_to_recover"] = crep.mean_ticks_to_recover

    # -- fleet failure domains (DESIGN.md §10) -------------------------------
    # the multi-pod control tick on the LUT fast path: fan-out poll, two
    # pod decides off slices of one shared RailField, one global settle.
    # Pure numpy + one thermal solve per tick -> gated like the flat tick.
    from repro.ft.elastic import ElasticActuator, ElasticWorkAssignment
    from repro.launch.mesh import PodTopology

    n = rt.substrate.n_domains
    fleet2 = ctl.FleetActuator.from_runtime(rt, field=field)
    elastic = ElasticActuator(ElasticWorkAssignment(n))
    fan = ctl.FanoutTelemetry(fleet2)
    efan = ctl.FanoutTelemetry(elastic)
    amb2 = ctl.AmbientSensor(25.0)
    ctx = ctl.TickContext()
    pods = []
    for i, (lo, hi) in enumerate(PodTopology.partition(n, 2)):
        bus = ctl.TelemetryBus([amb2, fan.view(lo, hi, primary=(i == 0)),
                                efan.view(lo, hi)])
        pc = ctl.LutController(ctl.PodPlanner(rt.planner, lo, hi, ctx=ctx),
                               field=field.slice_chips(lo, hi))
        pods.append(ctl.PodDomain(i, lo, hi, bus, pc,
                                  ctl.PodRailChannel(fleet2, lo, hi)))
    floop = ctl.FleetLoop(pods, fleet2, elastic=elastic, ctx=ctx)
    floop.step(now=0.0)  # cold start: both pods share one memoized solve
    iters = 5
    t0 = time.perf_counter()
    for k in range(iters):
        amb2.trace = 25.0 + 0.1 * (k + 1)
        floop.step(now=1.0 + k)
    out["fleet_tick_us"] = (time.perf_counter() - t0) / iters * 1e6

    # pod failover: the quarantine actuation end to end — drop staged rail
    # writes and pin the slice to safe state, condemn the pod's chips onto
    # the survivors, drain the pod engine's active slots + queue to the
    # shared host page pool and resubmit round-robin.  Deterministic work
    # (page-exact gathers dominate), so the --check gate pins it.
    from repro.serve.cache import HostPagePool
    pool = HostPagePool()
    for pod in pods[:2]:
        pod.engine = Engine(model, params, batch_slots=2, max_len=64,
                            eos_id=-1, warmup=False, pool=pool)
    for rid in range(4):
        pods[1].engine.submit(
            Request(100 + rid, np.arange(6) % cfg.vocab_size, max_new=48))
    pods[1].engine.step()  # two active mid-decode, two queued
    lat = []
    for k in range(-1, 3 if quick else 8):  # round -1: untimed compile
        t0 = time.perf_counter()
        floop._quarantine(pods[1], now=11.0 + k, events=[])
        if k >= 0:
            lat.append(time.perf_counter() - t0)
        # untimed: undo for the next round (restore shares + rail pins,
        # hand the migrated requests back to the victim pod)
        floop._restore(pods[1], now=11.5 + k, events=[])
        back = pods[0].engine.drain()
        for req in back:
            pods[1].engine.submit(req)
        pods[1].engine.step()
    out["pod_failover_ms"] = float(np.mean(lat)) * 1e3
    return out


REGRESSION_FACTOR = 2.0  # --check fails past this ratio (CI machine slack)

# throughput/rate entries gate in the OPPOSITE direction: current must not
# fall below baseline / REGRESSION_FACTOR (the serving acceptance floor —
# e.g. a paged-path tokens/s collapse or a dead speculative accept rate)
LOWER_BOUND_KEYS = ("serve_tokens_per_s", "spec_decode_accept_rate")


def _gated(k: str) -> bool:
    """jnp-path ``*_us`` entries plus the warm RailField build are gated;
    interpret-mode and load-dependent latency entries are not."""
    if k == "railfield_build_ms":  # warm device-call-bound: stable
        return True
    if k == "pod_failover_ms":  # deterministic containment actuation
        return True
    if k == "mean_ticks_to_recover":  # deterministic chaos-day replay:
        return True                   # a drift here is a logic change
    if k in LOWER_BOUND_KEYS:
        return True
    return k.endswith("_us") and "interpret" not in k


def check_regressions(baseline: Dict, current: Dict,
                      factor: float = REGRESSION_FACTOR):
    """Compare gated entries against the committed baseline.

    Interpret-mode entries are structural (the CPU interpreter's wall time
    says nothing about TPU perf) and throughput/latency entries of the
    closed-loop benchmark are load-dependent; the stable regression signal
    is the jnp-reference kernel + solver timings, plus the warm RailField
    build and fast-path lookup (``railfield_build_ms`` /
    ``railfield_lookup_us``).  ``LOWER_BOUND_KEYS`` (paged-path serving
    throughput, speculative accept rate) gate downward instead: they fail
    when the current value drops below ``baseline / factor``. Returns
    offending
    ``(key, baseline, current)`` rows and the baseline keys absent from
    the current results (a missing key would otherwise silently disable
    its gate — the caller must treat it as a failure)."""
    bad, missing = [], []
    for k in sorted(baseline):
        if not _gated(k):
            continue
        if k not in current:
            missing.append(k)
        elif k in LOWER_BOUND_KEYS:
            if current[k] < baseline[k] / factor:
                bad.append((k, baseline[k], current[k]))
        elif current[k] > baseline[k] * factor:
            bad.append((k, baseline[k], current[k]))
    return bad, missing


def main(argv=None) -> None:
    """CI smoke entry: ``python benchmarks/kernels_bench.py --smoke``."""
    import argparse
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced shapes; assert every kernel runs")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump results as JSON (the CI artifact); with "
                         "--check (and no --smoke), an existing file here "
                         "is reused as the current numbers")
    ap.add_argument("--check", default=None, metavar="BASELINE",
                    help="fail on >2x regression of any jnp-path *_us "
                         "entry vs this baseline JSON (BENCH_kernels.json)")
    args = ap.parse_args(argv)

    if (args.check and not args.smoke and args.json
            and os.path.exists(args.json)):
        with open(args.json) as f:  # reuse the artifact just benchmarked
            res = json.load(f)
    else:
        # the committed baseline is produced by --smoke, so a --check run
        # must measure smoke shapes too (full shapes are 4-5x slower and
        # would trip the gate spuriously)
        smoke = args.smoke or bool(args.check)
        res = run(quick=smoke)
        if smoke:
            res.update(closed_loop(quick=True))
        for k, v in res.items():
            print(f"{k},{v:.4g}" if v < 100 else f"{k},{v:.0f}")
        if args.json:
            with open(args.json, "w") as f:
                json.dump(res, f, indent=2, sort_keys=True)
            print(f"[json] wrote {args.json}")
        assert all(v > 0 for v in res.values())

    if args.check:
        with open(args.check) as f:
            baseline = json.load(f)
        bad, missing = check_regressions(baseline, res)
        for k, b, c in bad:
            print(f"[check] REGRESSION {k}: {b:.1f} -> {c:.1f} us "
                  f"({c / b:.2f}x)")
        for k in missing:
            print(f"[check] MISSING {k}: in {args.check} but not in the "
                  f"current results (rename it in both, or refresh the "
                  f"baseline)")
        if bad or missing:
            sys.exit(1)
        n = sum(1 for k in baseline if _gated(k))
        print(f"[check] OK: {n} gated entries within "
              f"{REGRESSION_FACTOR}x of {args.check}")


if __name__ == "__main__":
    main()
