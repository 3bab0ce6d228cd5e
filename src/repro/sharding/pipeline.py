"""Pipeline parallelism: GPipe-style microbatch streaming over a mesh axis.

``pipeline_apply`` maps a stack of layer groups (stages) onto a mesh axis
with ``shard_map`` + ``ppermute``: each device holds one stage's weights and,
per schedule tick, runs its stage on the microbatch it holds, then passes
activations to the next stage. With M microbatches and P stages the schedule
runs M + P - 1 ticks (bubble fraction (P-1)/(M+P-1), the GPipe bound).

On the production meshes the ``pod`` axis is the natural pipeline axis
(2 stages across pods — inter-pod links are the slow ones, and PP sends only
activations across them once per microbatch, not gradients per layer).
Exercised on host devices by tests/test_pipeline.py.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x, mesh: Mesh,
                   axis: str, n_microbatches: int):
    """Run ``stage_fn(params_i, x) -> x`` through P pipeline stages.

    stage_params: pytree stacked on a leading axis of size P (sharded over
    ``axis``); x: (B, ...) global batch, B % n_microbatches == 0.
    Returns stage_{P-1}(...stage_0(x)) for every microbatch, reassembled.
    """
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_microbatches == 0
    mb = B // n_microbatches
    ticks = n_microbatches + n_stages - 1

    def spmd(params, xs):
        # params: this device's stage params (leading dim 1); xs: (M, mb, ...)
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        idx = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xs[0])  # activation held by this stage
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when available)
            feed = jnp.where(t < n_microbatches, t, 0)
            buf = jnp.where(idx == 0, xs[feed], buf)
            buf = stage_fn(params, buf)
            # last stage emits microbatch (t - (P-1))
            out_t = t - (n_stages - 1)
            emit = jnp.where(out_t >= 0, out_t, 0)
            outs = jnp.where(
                (idx == n_stages - 1) & (out_t >= 0),
                outs.at[emit].set(buf), outs)
            # pass activations downstream (ring; stage P-1 -> 0 is ignored)
            buf = jax.lax.ppermute(
                buf, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # replicate the last stage's outputs to all shards
        outs = jax.lax.all_gather(outs, axis)[n_stages - 1]
        return outs

    in_specs = (jax.tree_util.tree_map(lambda _: P(axis), stage_params),
                P())
    fn = jax.shard_map(spmd, mesh=mesh, in_specs=in_specs, out_specs=P(),
                       check_vma=False)
    xs = x.reshape((n_microbatches, mb) + x.shape[1:])
    outs = fn(stage_params, xs)
    return outs.reshape(x.shape)
