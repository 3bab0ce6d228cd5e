"""The plain Qwen3 reference against the program, at a CPU size.

The same weights, drawn from one seed and laid out for each side by the
configuration's ``program_params`` table, give the program's ``Model.apply``
logits and its training loss and gradients (z-loss included, as the
trainer adds it) to float32 round-off.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import harness, weights
from benchmarks.chip.tests import chipbench_small as S

REF = harness.load_module(os.path.join(S.ROOT, "reference", "qwen3.py"),
                          "bench_ref_qwen3")


def _program(cfg_json):
    from repro.models.model import Model
    cfg_json = dict(cfg_json)
    cfg_json["program"] = dict(cfg_json["program"])
    cfg_json["program"]["fields"] = {**cfg_json["program"]["fields"],
                                     "param_dtype": "float32",
                                     "dtype": "float32", "remat": "none"}
    return Model(harness.program_config(cfg_json))


@pytest.fixture(scope="module")
def setup():
    c = S.small_config()
    model = _program(c)
    specs = REF.param_specs(c)
    seed = 2**32 + 17
    p = weights.program_tree(specs, seed, "float32", c["program_params"],
                             model.abstract_params())
    w = weights.canonical(specs, seed, "float32")
    toks = np.random.default_rng(3).integers(0, c["vocab_size"], (2, 24))
    return c, model, p, w, jnp.asarray(toks, jnp.int32)


def test_logits_match_model_apply(setup):
    c, model, p, w, toks = setup
    got, _ = model.apply(p, {"tokens": toks})
    want = jnp.stack([REF.logits(w, t, c) for t in toks])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_loss_and_grads_match_train_step(setup):
    from repro.train.step import make_loss_fn
    c, model, p, w, toks = setup
    labels = jnp.roll(toks, -1, axis=1)
    (got_loss, _), got_g = jax.value_and_grad(make_loss_fn(model),
                                              has_aux=True)(
        p, {"tokens": toks, "labels": labels})
    want_loss, want_g = REF.grad(w, toks, labels, c, z_coef=1e-4)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * abs(
        float(want_loss))
    flat, _ = jax.tree_util.tree_flatten_with_path(got_g)
    for path, g in flat:
        name = c["program_params"][".".join(str(k.key) for k in path)]
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(want_g[name]).reshape(g.shape),
            rtol=1e-3, atol=1e-6, err_msg=name)


def test_fp8_control_departs_from_reference(setup):
    c, model, p, w, toks = setup
    exact = REF.logits(w, toks[0], c)
    low = REF.logits(w, toks[0], c, mm=REF.fp8)
    rel = float(jnp.max(jnp.abs(low - exact)) / jnp.max(jnp.abs(exact)))
    assert 1e-3 < rel < 0.5
