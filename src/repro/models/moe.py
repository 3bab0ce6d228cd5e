"""Mixture-of-Experts: the gate, a capacity-bounded scatter dispatch for
training (:func:`moe_apply`), and a dropless layer over the experts a chip
holds for serving (:func:`moe_dropless`).

The gate scores all ``num_experts`` (softmax of float32 logits), limited to
the best groups where the configuration says so (DeepSeek-V2); a chip holds
``cfg.n_held`` of the experts from ``cfg.held_expert_start`` and computes
only the picks that land on them.

Dispatch is scatter/gather based (GShard-style positions via one-hot cumsum)
rather than the (tokens × experts × capacity) one-hot einsum — the dispatch
tensors stay O(tokens·k), which is what makes deepseek-v2 (160 experts) fit.

Shard-local grouping (§Perf iteration 2): tokens are reshaped to
(G_loc, n_dp, gs, D) where the n_dp axis carries the data sharding, so each
dispatch group lives entirely on one shard — routing, capacity positions,
scatter and combine are communication-free; the only collectives left are the
mathematically-required expert contractions ('tp' mode: hidden-dim psum;
'ep' mode: token movement to expert shards). The earlier strided grouping
spanned shards and pushed dispatch buffers through data-axis all-reduces
(7.4 TB/chip/step on mixtral train_4k — §Perf log).

Expert sharding comes from the plan: 'ep' (experts over model axis, e.g.
deepseek-v2 160/16) or 'tp' (hidden dim over model axis, e.g. mixtral 8<16).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamMeta, dense
from repro.models import layers
from repro.sharding.plan import Plan


def moe_params(cfg: ModelConfig, plan: Plan):
    """The router scores all ``num_experts``; the expert weights are those
    of the experts this chip holds (``cfg.n_held``)."""
    d, ff, E = cfg.d_model, cfg.moe_d_ff, cfg.n_held
    p = {
        "router": dense(d, cfg.num_experts, "embed", None),
        "wg": ParamMeta((E, d, ff), ("experts", "embed", "expert_ffn"), fan_in=d),
        "wu": ParamMeta((E, d, ff), ("experts", "embed", "expert_ffn"), fan_in=d),
        "wd": ParamMeta((E, ff, d), ("experts", "expert_ffn", "embed"), fan_in=ff),
    }
    if cfg.num_shared_experts:
        p["shared"] = layers.mlp_params(
            cfg, d_ff=cfg.num_shared_experts * cfg.moe_d_ff)
    return p


def gate_args(cfg: ModelConfig) -> dict:
    """The configuration's gate settings, as :func:`router_topk` takes them."""
    return {"n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor}


def router_topk(logits, k: int, n_group: int = 0, topk_group: int = 0,
                norm_topk_prob: bool = True,
                routed_scaling_factor: float = 1.0):
    """Softmax over all experts, then top-k (+ aux losses).

    With ``n_group``, the experts fall in ``n_group`` equal groups and a
    token picks only inside the ``topk_group`` groups whose best score is
    highest (DeepSeek-V2's ``group_limited_greedy``).  The k weights are the
    picks' softmax scores, renormalised to sum to 1 or, with
    ``norm_topk_prob`` False, times ``routed_scaling_factor``.

    logits: (..., E); weights/idx: (..., k); aux/z are scalars (mean over
    all leading dims)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    E = logits.shape[-1]
    scores = probs
    if n_group:
        per = E // n_group
        best = jnp.max(probs.reshape(probs.shape[:-1] + (n_group, per)), -1)
        _, gi = jax.lax.top_k(best, topk_group)
        kept = jnp.sum(jax.nn.one_hot(gi, n_group, dtype=jnp.int32), -2) > 0
        scores = jnp.where(jnp.repeat(kept, per, axis=-1), probs, 0.0)
    w, idx = jax.lax.top_k(scores, k)
    if norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-9)
    else:
        w = w * routed_scaling_factor
    lead = tuple(range(logits.ndim - 1))
    me = jnp.mean(probs, axis=lead)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=-2),
                  axis=lead) / k
    aux = E * jnp.sum(me * ce)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits.astype(jnp.float32), -1)))
    return w, idx, aux, z


def _router_logits(p, x):
    """Router logits in float32, as the published gates compute them."""
    return jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                      p["router"].astype(jnp.float32))


def _held(idx, cfg: ModelConfig):
    """Each pick's index among the experts this chip holds, and whether it
    is held at all."""
    local = idx - cfg.held_expert_start
    return local, (local >= 0) & (local < cfg.n_held)


def _dispatch_batched(p, x, cfg: ModelConfig, plan: Plan, capacity: int):
    """x: (n, gs, D) — n shard-local groups. Returns (out, aux, z)."""
    n, T, D = x.shape
    E, k = cfg.n_held, cfg.num_experts_per_tok
    dt = x.dtype
    logits = _router_logits(p, x)
    w, idx, aux, z = router_topk(logits, k, **gate_args(cfg))  # (n,T,k)
    local, held = _held(idx, cfg)

    flat_e = jnp.where(held, local, 0).reshape(n, T * k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (n,T*k,E)
    onehot = onehot * held.reshape(n, T * k, 1)
    pos_in_e = jnp.cumsum(onehot, axis=1) - onehot
    pos = jnp.take_along_axis(pos_in_e, flat_e[..., None], axis=2)[..., 0]
    keep = (pos < capacity) & held.reshape(n, T * k)
    slot = jnp.where(keep, flat_e * capacity + pos, E * capacity)  # (n,T*k)

    tok_idx = jnp.repeat(jnp.arange(T), k)  # (T*k,)
    xs = jnp.take(x, tok_idx, axis=1)  # (n,T*k,D)
    xs = xs * keep[..., None].astype(dt)

    def scatter_one(xs_i, slot_i):
        return jnp.zeros((E * capacity + 1, D), dt).at[slot_i].add(xs_i)[:-1]

    buf = jax.vmap(scatter_one)(xs, slot).reshape(n, E, capacity, D)
    buf = plan.act(buf, "batch", "experts", None, None)

    # expert FFN (SwiGLU), batched over groups x experts
    g = jnp.einsum("necd,edf->necf", buf, p["wg"].astype(dt))
    u = jnp.einsum("necd,edf->necf", buf, p["wu"].astype(dt))
    h = jax.nn.silu(g) * u
    h = plan.act(h, "batch", "experts", None, "expert_ffn")
    out_buf = jnp.einsum("necf,efd->necd", h, p["wd"].astype(dt))
    out_buf = plan.act(out_buf, "batch", "experts", None, None)

    flat = out_buf.reshape(n, E * capacity, D)
    safe_slot = jnp.minimum(slot, E * capacity - 1)
    gathered = jnp.take_along_axis(flat, safe_slot[..., None], axis=1)
    gathered = gathered * (keep[..., None]
                           * w.reshape(n, T * k)[..., None]).astype(dt)
    out = jnp.sum(gathered.reshape(n, T, k, D), axis=2)
    return out, aux, z


def moe_apply(p, x, cfg: ModelConfig, plan: Plan) -> Tuple[jax.Array, Dict]:
    """x: (B,S,D) -> (out, {aux, z}) with shared experts added."""
    B, S, D = x.shape
    T = B * S
    n_dp = 1
    if plan.mesh is not None and plan.dp_axes and not plan.replicate_batch:
        import numpy as _np
        n_dp = int(_np.prod([plan.mesh.shape[a] for a in plan.dp_axes]))
        if B % n_dp != 0:
            n_dp = 1
    gs = cfg.moe_group_size or T
    gs = min(gs, T // n_dp)
    g_loc = (T // n_dp) // gs
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    capacity = max(int(gs * k * cfg.moe_capacity_factor / E), 4)

    xf = x.reshape(T, D).reshape(n_dp, g_loc, gs, D).swapaxes(0, 1)
    # (G_loc, n_dp, gs, D): scan axis unsharded, dim 1 carries data sharding
    xf = plan.act(xf, None, "batch", None, None)

    def body(_, xg):
        out, aux, z = _dispatch_batched(p, xg, cfg, plan, capacity)
        return None, (out, aux, z)

    if g_loc == 1:
        o, aux, z = _dispatch_batched(p, xf[0], cfg, plan, capacity)
        outs, auxs, zs = o[None], aux[None], z[None]
    else:
        _, (outs, auxs, zs) = jax.lax.scan(body, None, xf)

    out = outs.swapaxes(0, 1).reshape(B, S, D)
    if cfg.num_shared_experts:
        out = out + layers.mlp_apply(p["shared"], x, cfg, plan)
    losses = {"moe_aux": jnp.mean(auxs), "moe_z": jnp.mean(zs)}
    return out, losses


def moe_dropless(p, x, cfg: ModelConfig, plan: Plan, layer, valid=None):
    """The served expert layer: dropless, over the experts this chip holds.

    Routing runs over all ``num_experts``.  The picks that land on held
    experts are sorted by expert and go through grouped matmuls
    (``jax.lax.ragged_dot``) over the held experts, with no capacity: every
    held pick is computed however skewed the routing.  Picks of experts
    held elsewhere, and tokens outside ``valid`` (B, S) (a ragged tick's
    padded tails), add nothing here; on one chip there is no exchange.  The
    shared experts run for every token.

    The expert weights ``wg``/``wu``/``wd`` in ``p`` are every expert
    layer's, stacked (L, E, ...), and ``layer`` names this one: its experts
    are its groups of the grouped matmuls over the whole stack.  The kernel
    reads only groups that hold rows, whereas a slice of the stack taken
    inside the layer scan would be copied for it on every call.

    x: (B,S,D) -> (out, stats): ``routed_local`` counts the held picks of
    valid tokens, ``experts_touched`` the held experts given at least one.
    """
    B, S, D = x.shape
    T, k, E = B * S, cfg.num_experts_per_tok, cfg.n_held
    dt = x.dtype
    xf = x.reshape(T, D)
    with jax.named_scope("moe_route"):
        w, idx, _, _ = router_topk(_router_logits(p, xf), k, **gate_args(cfg))
        local, held = _held(idx, cfg)  # (T,k)
        if valid is not None:
            held = held & valid.reshape(T, 1)
        held = held.reshape(T * k)
        # expert E = computed elsewhere or not at all: sorted past the groups
        e = jnp.where(held, local.reshape(T * k), E)
        order = jnp.argsort(e, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(e, E, dtype=jnp.int32), axis=0)
        rows = jnp.take(xf, order // k, axis=0)  # (T*k, D), by expert
    with jax.named_scope("moe_experts"):
        n_layers = p["wg"].shape[0]
        w_ex = {n: p[n].astype(dt).reshape((n_layers * E,) + p[n].shape[2:])
                for n in ("wg", "wu", "wd")}
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros(n_layers * E, sizes.dtype), sizes, (layer * E,))
        g = jax.lax.ragged_dot(rows, w_ex["wg"], groups)
        u = jax.lax.ragged_dot(rows, w_ex["wu"], groups)
        y = jax.lax.ragged_dot(jax.nn.silu(g) * u, w_ex["wd"], groups)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=order.dtype))
        y = jnp.take(y, inv, axis=0)  # back in (token, pick) order
        wk = jnp.where(held, w.reshape(T * k), 0.0)[:, None]
        y = jnp.where(held[:, None], y.astype(jnp.float32), 0.0) * wk
        out = jnp.sum(y.reshape(T, k, D), axis=1).astype(dt).reshape(B, S, D)
    if cfg.num_shared_experts:
        with jax.named_scope("moe_shared"):
            out = out + layers.mlp_apply(p["shared"], x, cfg, plan)
    stats = {"routed_local": jnp.sum(held, dtype=jnp.int32),
             "experts_touched": jnp.sum(sizes > 0, dtype=jnp.int32)}
    return out, stats
