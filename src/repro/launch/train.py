"""End-to-end training driver (CPU-runnable; production flags wired through).

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
        --steps 50 --energy-policy power_save --checkpoint-dir /tmp/ckpt

Features exercised here are the production ones: jit'd train_step with
plan shardings on a host mesh, deterministic data pipeline, async
checkpointing + restore (--resume), failure injection + bounded retry,
straggler detection, and the EnergyAwareRuntime (paper technique) reporting
per-step fleet savings from the step's measured utilization profile.

With ``--energy-policy`` the run closes the loop through ``repro.control``:
step times feed the straggler detector, whose events route through the
``LutController`` (rail-boost-or-rebalance becomes a policy decision), and
a ``FleetActuator`` applies rails + reports the thermal readout each
control tick.
"""
from __future__ import annotations

import argparse
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import control as ctl
from repro import policy as pol
from repro.checkpoint.manager import CheckpointManager
from repro.configs import registry
from repro.core import runtime as energy_rt
from repro.core import tpu_fleet as TF
from repro.data.pipeline import DataConfig, make_iterator
from repro.ft.elastic import ElasticActuator, ElasticWorkAssignment
from repro.ft.monitor import (FailureInjector, StragglerDetector,
                              TransientError, retry_step)
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import PodTopology, make_host_mesh
from repro.models import params as pm
from repro.models.model import Model
from repro.sharding.plan import make_plan
from repro.train.optimizer import make_optimizer
from repro.train.step import make_train_step


def build(cfg, mesh, n_accum: int = 1):
    """Wire ``cfg`` onto ``mesh``: plan, model, optimizer, the jitted
    train step (plan shardings, params and optimizer state donated), and
    ``init(key) -> (params, opt_state)``, which materializes both directly
    in their shardings (never whole on one device)."""
    plan = make_plan(cfg, mesh)
    model = Model(cfg, plan)
    opt = make_optimizer(cfg, total_steps=10_000)
    step_fn = make_train_step(model, opt, n_accum=n_accum)
    meta = model.param_meta()

    param_sh = plan.param_shardings(meta)
    opt_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        plan.param_specs(opt.state_meta(meta)),
        is_leaf=lambda x: isinstance(x, P))
    jit_step = jax.jit(step_fn, in_shardings=(param_sh, opt_sh, None, None),
                       donate_argnums=(0, 1))

    def init(key):
        params = jax.jit(model.init, out_shardings=param_sh)(key)
        return params, jax.jit(opt.init, out_shardings=opt_sh)(params)

    return plan, model, opt, jit_step, init


def main(argv=None) -> List[float]:
    """Train from the command line; returns each step's loss."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-accum", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--inject-failure-at", type=int, default=-1)
    ap.add_argument("--energy-policy", default="off",
                    help="off | power_save | min_energy | overscale:<g>")
    ap.add_argument("--t-amb", type=float, default=25.0,
                    help="ambient degC the control plane senses")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)
    use_compile_cache()

    mesh = make_host_mesh(model=args.model_parallel)
    cfg = registry.get(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    plan, model, opt, jit_step, init = build(cfg, mesh, args.n_accum)
    print(f"[train] arch={cfg.name} params={model.n_params():,} "
          f"mesh={dict(mesh.shape)}")

    params, opt_state = init(jax.random.PRNGKey(0))

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    global_batch=args.batch)
    start_step = 0

    ckpt = CheckpointManager(args.checkpoint_dir) if args.checkpoint_dir else None
    if ckpt and args.resume and ckpt.latest_step() is not None:
        state_like = {"params": params, "opt": opt_state}
        restored, start_step = ckpt.restore(state_like)
        params, opt_state = restored["params"], restored["opt"]
        print(f"[train] resumed from step {start_step}")

    it = make_iterator(cfg, dc, start_step=start_step)
    injector = FailureInjector(
        fail_at={args.inject_failure_at} if args.inject_failure_at >= 0 else set())
    straggler = StragglerDetector()

    # paper technique: fleet energy controller fed by the step profile;
    # the CLI spec becomes a first-class repro.policy Policy object, and the
    # telemetry->controller->actuator loop closes over the same planner
    rt: Optional[energy_rt.EnergyAwareRuntime] = None
    loop: Optional[ctl.ControlLoop] = None
    if args.energy_policy != "off":
        prof = TF.StepProfile.from_roofline(
            compute_s=0.7, memory_s=0.4, collective_s=0.15)
        rt = energy_rt.EnergyAwareRuntime(
            prof, policy=pol.from_spec(args.energy_policy),
            t_amb=args.t_amb)
        # straggler workers resolve to pod coordinates through the mesh
        # topology (out-of-pod ranks surface as unmapped, never chip 0),
        # and Rebalance decisions actually migrate work via the elastic
        # assignment, whose shares feed the RailField utilization axis
        topo = PodTopology(grid=rt.substrate.grid)
        mon = ctl.MonitorTelemetry(straggler, topology=topo)
        elastic = ElasticActuator(ElasticWorkAssignment(
            rt.substrate.n_domains))
        controller = rt.controller()  # per-chip RailField fast path
        fleet = ctl.FleetActuator.from_runtime(rt, field=controller.field)
        loop = ctl.ControlLoop(
            ctl.TelemetryBus([ctl.AmbientSensor(args.t_amb), mon, elastic,
                              fleet]),
            controller, [fleet, elastic])

    step = start_step
    losses = []
    t_train0 = time.time()
    while step < args.steps:
        batch = next(it)

        def do_step():
            injector.maybe_fail(step)
            return jit_step(params, opt_state, batch, jnp.int32(step))

        def on_fail(attempt, e):
            print(f"[ft] step {step} attempt {attempt} failed: {e}; retrying")

        t0 = time.time()
        params, opt_state, metrics = retry_step(do_step, on_failure=on_fail)
        losses.append(float(metrics["loss"]))
        dt = time.time() - t0
        ev = straggler.record("worker0", step, dt)
        if ev:
            print(f"[ft] straggler: step {ev.step} {ev.ratio:.2f}x median")

        if step % args.log_every == 0 or step == args.steps - 1:
            msg = (f"[train] step {step}: loss={float(metrics['loss']):.4f} "
                   f"acc={float(metrics['accuracy']):.3f} "
                   f"gnorm={float(metrics['grad_norm']):.2f} ({dt:.2f}s)")
            if loop is not None:
                # control tick: straggler events become policy decisions
                # (rail boost / rebalance), rails land on the actuator.
                # the energy line reads the controller's own plan — LUT
                # ticks must not pay a fixed point just to print a log
                rep = loop.step(now=float(step))
                for a in rep.actions:
                    if isinstance(a, (ctl.BoostRail, ctl.Rebalance)):
                        print(f"[ctl] {a}")
                rails = next(a for a in rep.actions
                             if isinstance(a, ctl.SetRails))
                p, ro = loop.controller.plan, rep.readout
                msg += (f" | energy[{args.energy_policy}]: "
                        f"save={p.saving*100:.1f}% Tmax={ro.t_max:.0f}C"
                        f" | ctl[{rails.source}]")
            elif rt is not None:  # planner without the loop (not wired)
                p = rt.plan()
                msg += (f" | energy[{args.energy_policy}]: "
                        f"save={p.saving*100:.1f}% Tmax={p.t_max:.0f}C")
            print(msg)

        if ckpt and (step + 1) % args.checkpoint_every == 0:
            ckpt.save(step + 1, {"params": params, "opt": opt_state},
                      metadata={"arch": cfg.name})
        step += 1

    if ckpt:
        ckpt.wait()
    print(f"[train] done: {args.steps - start_step} steps in "
          f"{time.time() - t_train0:.1f}s; final loss "
          f"{losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
