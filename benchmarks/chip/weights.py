"""Weights from the seed, made on the device in one jitted call.

The harness makes the weights, not the program: a configuration's reference
names them (``param_specs``), this module draws them from ``--seed`` in the
dtype they are served in, and a configuration's ``program_params`` table
lays the same values out as the program's parameter tree.  The reference
later draws them again from the seed, so it takes nothing the program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _draw(key, shape, init, dtype):
    kind, arg = init
    z = jax.random.normal(key, shape, jnp.float32)
    v = 1.0 + arg * z if kind == "norm" else arg * z
    return v.astype(dtype)


def canonical(specs: dict, seed: int, dtype: str, out_dtype: str = None):
    """name -> array, drawn from ``seed`` and rounded to ``dtype``; then
    held as ``out_dtype`` (the reference upcasts the served values)."""
    names = sorted(specs)
    out = jnp.dtype(out_dtype or dtype)

    @jax.jit
    def make(key):
        return {n: _draw(jax.random.fold_in(key, i), specs[n][0], specs[n][1],
                         dtype).astype(out)
                for i, n in enumerate(names)}

    return make(seed_key(seed))


def program_tree(specs: dict, seed: int, dtype: str, layout: dict, abstract):
    """The program's parameter tree (shapes from ``abstract``), each leaf the
    canonical weight that ``layout`` (``"a.b.c"`` path -> name) names,
    reshaped; all in one jitted call."""
    names = sorted(specs)
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = [".".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    missing = [p for p in paths if p not in layout]
    if missing:
        raise KeyError(f"program parameters with no canonical weight: "
                       f"{missing}")

    @jax.jit
    def make(key):
        w = {n: _draw(jax.random.fold_in(key, i), specs[n][0], specs[n][1],
                      dtype) for i, n in enumerate(names)}
        leaves = [w[layout[p]].reshape(leaf.shape).astype(leaf.dtype)
                  for p, (_, leaf) in zip(paths, flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make(seed_key(seed))
