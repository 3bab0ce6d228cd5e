"""Pipeline parallelism: GPipe schedule == sequential execution (subprocess
with 4 host devices so this process stays at 1 device)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.sharding.pipeline import pipeline_apply

mesh = jax.make_mesh((4,), ("pp",))
P_STAGES, D = 4, 16
key = jax.random.PRNGKey(0)
ws = jax.random.normal(key, (P_STAGES, D, D)) * (0.5 / np.sqrt(D))
bs = jax.random.normal(jax.random.fold_in(key, 1), (P_STAGES, D)) * 0.1
params = {"w": ws, "b": bs}

def stage(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])

x = jax.random.normal(jax.random.fold_in(key, 2), (8, D))

# sequential reference
ref = x
for i in range(P_STAGES):
    ref = stage({"w": ws[i], "b": bs[i]}, ref)

for M in (2, 4, 8):
    out = pipeline_apply(stage, params, x, mesh, "pp", n_microbatches=M)
    err = float(jnp.abs(out - ref).max())
    assert err < 1e-5, (M, err)
print("PIPELINE_OK")
"""


@pytest.mark.slow
def test_gpipe_schedule_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # host devices; never a second chip user
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "PIPELINE_OK" in out.stdout, (out.stdout[-500:], out.stderr[-2000:])
