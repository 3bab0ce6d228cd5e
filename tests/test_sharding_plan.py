"""Sharding plan invariants (no mesh needed) + 8-device mini dry-run via
subprocess (keeps this process at 1 device, per the assignment)."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.models import params as pm
from repro.models.model import Model
from repro.sharding import plan as plan_lib


class FakeMesh:
    """Just enough of Mesh for plan arithmetic without device init."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


def mk_plan(cfg, pod=False):
    shape = ({"pod": 2, "data": 16, "model": 16} if pod
             else {"data": 16, "model": 16})
    return plan_lib.make_plan(cfg, FakeMesh(shape))  # type: ignore


@pytest.mark.parametrize("arch", sorted(registry.ARCHS))
def test_dims_divisible_by_tp(arch):
    cfg = registry.get(arch)
    plan = mk_plan(cfg)
    assert plan.vocab % plan.tp == 0
    assert plan.vocab >= cfg.vocab_size
    if cfg.num_heads:
        assert plan.num_heads % plan.tp == 0
        assert plan.num_kv_heads % plan.tp == 0
        assert plan.num_heads >= cfg.num_heads
    if cfg.is_moe:
        if cfg.num_experts % plan.tp == 0:
            assert plan.expert_mode == "ep"
        else:
            assert plan.expert_mode == "tp"
            assert cfg.moe_d_ff % plan.tp == 0


def test_kv_repeat_rules():
    # GQA kv=8 with tp=16 -> repeated to 16
    plan = mk_plan(registry.get("llama3.2-1b"))
    assert plan.num_kv_heads == 16 and plan.kv_repeat == 2
    # whisper 12H: pad both q and kv to 16
    plan = mk_plan(registry.get("whisper-small"))
    assert plan.num_heads == 16 and plan.num_kv_heads == 16


@pytest.mark.parametrize("arch", ["deepseek-67b", "deepseek-v2-236b",
                                  "whisper-small"])
@pytest.mark.parametrize("pod", [False, True])
def test_param_specs_shard_consistently(arch, pod):
    cfg = registry.get(arch)
    plan = mk_plan(cfg, pod)
    model = Model(cfg, plan)
    meta = model.param_meta()
    axis_sizes = {"pod": 2, "data": 16, "model": 16}

    def check(m):
        spec = plan.param_spec(m)
        for dim, ax in zip(m.shape, tuple(spec) + (None,) * 8):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            total = int(np.prod([axis_sizes[a] for a in axes]))
            assert dim % total == 0, (m.shape, spec)

    pm.tree_map_meta(check, meta)


def test_fsdp_shards_large_params_over_dp():
    cfg = registry.get("deepseek-67b")
    plan = mk_plan(cfg)
    meta = Model(cfg, plan).param_meta()
    # embedding: vocab on model AND d_model on data (FSDP)
    emb = meta["embed"]["embedding"]
    spec = plan.param_spec(emb)
    assert spec[0] == "model"
    assert spec[1] == ("data",) or spec[1] == "data"


MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import registry
from repro.models.model import Model
from repro.models import params as pm
from repro.sharding.plan import make_plan
from repro.train.optimizer import make_optimizer
from repro.train.step import make_train_step

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = registry.get("llama3.2-1b").reduced().replace(
    num_heads=4, num_kv_heads=2, head_dim=16, d_model=64, d_ff=128)
plan = make_plan(cfg, mesh)
model = Model(cfg, plan)
opt = make_optimizer(cfg)
meta = model.param_meta()
step = make_train_step(model, opt, n_accum=2)

with mesh:
    params = model.init(jax.random.PRNGKey(0))
    params = jax.device_put(params, plan.param_shardings(meta))
    opt_state = jax.device_put(
        opt.init(params),
        jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                               plan.param_specs(opt.state_meta(meta)),
                               is_leaf=lambda x: isinstance(x, P)))
    batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
             "labels": jnp.zeros((8, 32), jnp.int32)}
    batch = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    p2, o2, m = jax.jit(step, donate_argnums=(0, 1))(params, opt_state,
                                                     batch, 0)
    assert jnp.isfinite(m["loss"])
print("MINI_DRYRUN_OK", float(m["loss"]))
"""


@pytest.mark.slow
def test_mini_mesh_train_step_subprocess():
    """Real 8-device SPMD train step (subprocess keeps this process at 1)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # host devices; never a second chip user
    out = subprocess.run([sys.executable, "-c", MINI_DRYRUN], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "MINI_DRYRUN_OK" in out.stdout, out.stderr[-2000:]

