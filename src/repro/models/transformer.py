"""Block assembly + full LM forward/prefill/decode for dense/moe/ssm/hybrid.

Repeated homogeneous layers are stacked and iterated with ``lax.scan`` (keeps
HLO size O(1) in depth — essential for the 512-device dry-run compiles) with
``jax.checkpoint`` around the block body when ``cfg.remat == 'full'``.

Heterogeneous stacks (zamba2 hybrid) scan over *groups*: each group is an
inner scan over ``hybrid_attn_every`` stacked mamba layers followed by the
single weight-shared attention block (captured, à la Zamba).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.params import stack_tree
from repro.sharding.plan import Plan

ZERO_AUX = lambda: {"moe_aux": jnp.zeros((), jnp.float32),
                    "moe_z": jnp.zeros((), jnp.float32)}


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    return fn


# =============================================================================
# single blocks
# =============================================================================

def attn_block_params(cfg: ModelConfig, plan: Plan, use_moe: bool, d_ff=None):
    p = {
        "ln1": L.norm_params(cfg),
        "ln2": L.norm_params(cfg),
        "attn": (attn.mla_params(cfg, plan) if cfg.attn_type == "mla"
                 else attn.gqa_params(cfg, plan)),
    }
    if use_moe:
        p["moe"] = moe_lib.moe_params(cfg, plan)
    else:
        p["mlp"] = L.mlp_params(cfg, d_ff=d_ff)
    return p


def attn_block_apply(p, x, cfg, plan, positions=None, collect_kv=False):
    h = L.norm_apply(p["ln1"], x, cfg)
    if cfg.attn_type == "mla":
        a, kv = attn.mla_apply(p["attn"], h, cfg, plan, positions)
    else:
        a, kv = attn.gqa_apply(p["attn"], h, cfg, plan, positions)
    x = x + a
    h = L.norm_apply(p["ln2"], x, cfg)
    if "moe" in p:
        m, aux = moe_lib.moe_apply(p["moe"], h, cfg, plan)
    else:
        m, aux = L.mlp_apply(p["mlp"], h, cfg, plan), ZERO_AUX()
    x = x + m
    x = plan.act(x, "batch", "seq", None)
    return (x, aux, kv) if collect_kv else (x, aux)


def attn_block_decode(p, x, cache, pos, cfg, plan, n_valid=None, pages=None,
                      experts=None):
    """One block's decode step; ``pages`` = (layer, block table) runs its
    attention against the page pool ``cache`` (:func:`attn.gqa_decode_paged`,
    :func:`attn.mla_decode_paged`).  An expert block runs the dropless
    layer over the experts this chip holds, given ``experts`` = (every
    layer's expert weights, this layer's index), as :func:`_split_experts`
    takes them out of the stack (:func:`moe_lib.moe_dropless`).  Returns
    (x, cache, stats): the expert layer's counters, empty for a dense
    block."""
    h = L.norm_apply(p["ln1"], x, cfg)
    if pages is not None:
        paged = (attn.mla_decode_paged if cfg.attn_type == "mla"
                 else attn.gqa_decode_paged)
        a, cache = paged(p["attn"], h, cache, *pages, pos, cfg, plan,
                         n_valid=n_valid)
    elif cfg.attn_type == "mla":
        a, cache = attn.mla_decode(p["attn"], h, cache, pos, cfg, plan,
                                   n_valid=n_valid)
    else:
        a, cache = attn.gqa_decode(p["attn"], h, cache, pos, cfg, plan,
                                   n_valid=n_valid)
    x = x + a
    h = L.norm_apply(p["ln2"], x, cfg)
    stats = {}
    if "moe" in p:
        S = x.shape[1]
        valid = (None if n_valid is None else
                 jnp.arange(S)[None] < jnp.asarray(n_valid)[:, None])
        weights, layer = experts
        m, stats = moe_lib.moe_dropless({**p["moe"], **weights}, h, cfg,
                                        plan, layer, valid)
    else:
        m = L.mlp_apply(p["mlp"], h, cfg, plan)
    return x + m, cache, stats


def ssm_block_params(cfg, plan):
    return {"ln": L.norm_params(cfg), "ssm": ssm_lib.ssm_params(cfg, plan)}


def ssm_block_apply(p, x, cfg, plan):
    h = L.norm_apply(p["ln"], x, cfg)
    o, state = ssm_lib.ssm_apply(p["ssm"], h, cfg, plan)
    return x + o, state


def ssm_block_decode(p, x, state, cfg, plan):
    h = L.norm_apply(p["ln"], x, cfg)
    o, state = ssm_lib.ssm_decode(p["ssm"], h, state, cfg, plan)
    return x + o, state


# =============================================================================
# homogeneous stacks (dense / moe / ssm): scan over stacked layer params
# =============================================================================

def _uniform_stack_params(cfg: ModelConfig, plan: Plan):
    if cfg.family == "ssm":
        one = ssm_block_params(cfg, plan)
        n_scan = cfg.num_layers
        extra = {}
    elif cfg.is_moe:
        one = attn_block_params(cfg, plan, use_moe=True)
        n_scan = cfg.num_layers - cfg.first_k_dense
        extra = {
            f"dense{i}": attn_block_params(cfg, plan, use_moe=False)
            for i in range(cfg.first_k_dense)
        }
    else:
        one = attn_block_params(cfg, plan, use_moe=False)
        n_scan = cfg.num_layers
        extra = {}
    return {"stack": stack_tree(one, n_scan), **extra}, n_scan


def _scan_blocks(stack_params, x, cfg, plan, block_fn):
    """scan over stacked params; block_fn(p, x) -> (x, aux_or_state)."""

    def body(carry, layer_p):
        x, aux = carry
        x, a = block_fn(layer_p, x)
        if isinstance(a, dict) and "moe_aux" in a:
            aux = {k: aux[k] + a[k] for k in aux}
            return (x, aux), None
        return (x, aux), a

    if not cfg.scan_layers:
        # unrolled python loop: same contract as the scan below, but the
        # block body runs eagerly layer by layer — required when matmuls
        # are routed through a host-side kernel (repro.tolerance ABFT),
        # which cannot execute under a scan trace.
        carry, states = (x, ZERO_AUX()), []
        n = jax.tree_util.tree_leaves(stack_params)[0].shape[0]
        for i in range(n):
            layer_p = jax.tree_util.tree_map(lambda v: v[i], stack_params)
            carry, s = body(carry, layer_p)
            states.append(s)
        x, aux = carry
        if states and states[0] is not None:
            states = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *states)
        else:
            states = None
        return x, aux, states

    body = _maybe_remat(body, cfg)
    (x, aux), states = jax.lax.scan(body, (x, ZERO_AUX()), stack_params)
    return x, aux, states


# =============================================================================
# top-level model params
# =============================================================================

def lm_params(cfg: ModelConfig, plan: Plan):
    p: Dict[str, Any] = {
        "embed": L.embed_params(cfg, plan),
        "final_ln": L.norm_params(cfg),
    }
    if cfg.family in ("dense", "moe", "ssm"):
        blocks, _ = _uniform_stack_params(cfg, plan)
        p["blocks"] = blocks
    elif cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        n_groups, rem = divmod(cfg.num_layers, k)
        p["blocks"] = {
            "groups": stack_tree(stack_tree(ssm_block_params(cfg, plan), k), n_groups),
            "shared_attn": attn_block_params(cfg, plan, use_moe=False),
            "tail": stack_tree(ssm_block_params(cfg, plan), rem) if rem else {},
        }
    else:
        raise ValueError(cfg.family)
    return p


# =============================================================================
# forward (train): logits + aux
# =============================================================================

def lm_apply(params, tokens, cfg: ModelConfig, plan: Plan):
    x = L.embed_apply(params["embed"], tokens, cfg, plan)
    aux = ZERO_AUX()

    if cfg.family in ("dense", "moe"):
        for i in range(cfg.first_k_dense):
            x, a = attn_block_apply(params["blocks"][f"dense{i}"], x, cfg, plan)
        x, a, _ = _scan_blocks(
            params["blocks"]["stack"], x, cfg, plan,
            lambda p, x: attn_block_apply(p, x, cfg, plan))
        aux = a
    elif cfg.family == "ssm":
        x, aux, _ = _scan_blocks(
            params["blocks"]["stack"], x, cfg, plan,
            lambda p, x: (ssm_block_apply(p, x, cfg, plan)[0], None))
    elif cfg.family == "hybrid":
        x, aux = _hybrid_apply(params["blocks"], x, cfg, plan)

    x = L.norm_apply(params["final_ln"], x, cfg)
    logits = L.unembed_apply(params["embed"], x, cfg, plan)
    return logits, aux


def _hybrid_apply(bp, x, cfg, plan):
    aux = ZERO_AUX()

    def group_body(carry, gp):
        x, aux = carry

        def inner(c, lp):
            return ssm_block_apply(lp, c, cfg, plan)[0], None

        x, _ = jax.lax.scan(inner, x, gp)
        x, a = attn_block_apply(bp["shared_attn"], x, cfg, plan)
        aux = {k: aux[k] + a[k] for k in aux}
        return (x, aux), None

    (x, aux), _ = jax.lax.scan(
        _maybe_remat(group_body, cfg), (x, aux), bp["groups"])
    if bp["tail"]:
        def inner(c, lp):
            return ssm_block_apply(lp, c, cfg, plan)[0], None
        x, _ = jax.lax.scan(inner, x, bp["tail"])
    return x, aux


# =============================================================================
# decode caches
# =============================================================================

def lm_cache(cfg: ModelConfig, plan: Plan, batch: int, max_len: int,
             dtype, abstract: bool = False):
    """Build (abstract or zero) decode cache pytree for the whole stack."""

    def attn_cache():
        if cfg.attn_type == "mla":
            return attn.mla_cache_init(cfg, plan, batch, max_len, dtype,
                                       abstract=abstract)
        if abstract:
            return attn.gqa_cache_abstract(cfg, plan, batch, max_len, dtype)
        return attn.gqa_cache_init(cfg, plan, batch, max_len, dtype)

    def ssm_state():
        return ssm_lib.ssm_state_init(cfg, plan, batch, dtype, abstract=abstract)

    def rep(tree, n):
        """stack a cache pytree n times along a new leading dim."""
        def do(leaf):
            if abstract:
                return jax.ShapeDtypeStruct((n,) + leaf.shape, leaf.dtype)
            return jnp.broadcast_to(leaf, (n,) + leaf.shape).copy()
        return jax.tree_util.tree_map(do, tree)

    if cfg.family in ("dense", "moe"):
        n_scan = cfg.num_layers - cfg.first_k_dense
        c = {"stack": rep(attn_cache(), n_scan)}
        for i in range(cfg.first_k_dense):
            c[f"dense{i}"] = attn_cache()
        return c
    if cfg.family == "ssm":
        return {"stack": rep(ssm_state(), cfg.num_layers)}
    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        n_groups, remn = divmod(cfg.num_layers, k)
        return {
            "groups": rep(rep(ssm_state(), k), n_groups),
            "shared_attn": rep(attn_cache(), n_groups),
            "tail": rep(ssm_state(), remn) if remn else {},
        }
    raise ValueError(cfg.family)


def lm_cache_specs(cfg: ModelConfig, plan: Plan, seq_axis=None):
    """PartitionSpec tree matching lm_cache structure."""
    from jax.sharding import PartitionSpec as P

    def add_layer_dim(tree):
        return jax.tree_util.tree_map(
            lambda s: P(*((None,) + tuple(s))), tree,
            is_leaf=lambda x: isinstance(x, P))

    if cfg.attn_type == "mla":
        a_spec = attn.mla_cache_spec(plan, seq_axis)
    else:
        a_spec = attn.gqa_cache_spec(plan, seq_axis)
    s_spec = ssm_lib.ssm_state_spec(plan)

    if cfg.family in ("dense", "moe"):
        c = {"stack": add_layer_dim(a_spec)}
        for i in range(cfg.first_k_dense):
            c[f"dense{i}"] = a_spec
        return c
    if cfg.family == "ssm":
        return {"stack": add_layer_dim(s_spec)}
    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        n_groups, remn = divmod(cfg.num_layers, k)
        return {
            "groups": add_layer_dim(add_layer_dim(s_spec)),
            "shared_attn": add_layer_dim(a_spec),
            "tail": add_layer_dim(s_spec) if remn else {},
        }
    raise ValueError(cfg.family)


# =============================================================================
# prefill: full forward that also seeds the decode cache
# =============================================================================

def _seed_attn_cache(cfg, plan, kv, max_len, dtype, batch, lengths=None):
    """Build a seeded per-layer cache directly from prefill K/V."""
    if cfg.attn_type == "mla":
        zero = attn.mla_cache_init(cfg, plan, batch, max_len, dtype)
        return attn.mla_seed_cache(zero, kv, kv.shape[1], lengths=lengths)
    zero = attn.gqa_cache_init(cfg, plan, batch, max_len, dtype)
    return attn.gqa_seed_cache(zero, kv, kv[0].shape[1], lengths=lengths)


def lm_prefill(params, tokens, cfg: ModelConfig, plan: Plan,
               max_len: Optional[int] = None, lengths=None):
    """tokens:(B,S) -> (logits, seeded cache with capacity max_len or S).

    ``lengths`` (B,) marks per-row true prompt lengths when the batch is
    right-padded: cache positions past a row's length record ``pos_id = -1``
    (attention families only — SSM/hybrid recurrent state has no position
    table, so ragged prefill there must run per-request at exact length).
    """
    B, S = tokens.shape
    max_len = max_len or S
    dtype = L.cdt(cfg)
    x = L.embed_apply(params["embed"], tokens, cfg, plan)
    cache: Dict[str, Any] = {}

    if cfg.family in ("dense", "moe"):
        for i in range(cfg.first_k_dense):
            x, _, kv = attn_block_apply(params["blocks"][f"dense{i}"], x, cfg,
                                        plan, collect_kv=True)
            cache[f"dense{i}"] = _seed_attn_cache(cfg, plan, kv, max_len,
                                                  dtype, B, lengths)

        def body(carry, lp):
            x = carry
            x, _, kv = attn_block_apply(lp, x, cfg, plan, collect_kv=True)
            return x, kv

        x, kvs = jax.lax.scan(_maybe_remat(body, cfg), x,
                              params["blocks"]["stack"])
        cache["stack"] = jax.vmap(
            lambda kv: _seed_attn_cache(cfg, plan, kv, max_len, dtype, B,
                                        lengths))(kvs)
    elif cfg.family == "ssm":
        def body(carry, lp):
            x, st = ssm_block_apply(lp, carry, cfg, plan)
            return x, st

        x, states = jax.lax.scan(_maybe_remat(body, cfg), x,
                                 params["blocks"]["stack"])
        cache["stack"] = states
    elif cfg.family == "hybrid":
        bp = params["blocks"]

        def group_body(carry, gp):
            x = carry

            def inner(c, lp):
                c, st = ssm_block_apply(lp, c, cfg, plan)
                return c, st

            x, sts = jax.lax.scan(inner, x, gp)
            x, _, kv = attn_block_apply(bp["shared_attn"], x, cfg, plan,
                                        collect_kv=True)
            return x, (sts, kv)

        x, (g_states, g_kvs) = jax.lax.scan(
            _maybe_remat(group_body, cfg), x, bp["groups"])
        cache["groups"] = g_states
        cache["shared_attn"] = jax.vmap(
            lambda kv: _seed_attn_cache(cfg, plan, kv, max_len, dtype, B,
                                        lengths))(g_kvs)
        if bp["tail"]:
            def inner(c, lp):
                c, st = ssm_block_apply(lp, c, cfg, plan)
                return c, st
            x, t_states = jax.lax.scan(inner, x, bp["tail"])
            cache["tail"] = t_states
        else:
            cache["tail"] = {}

    x = L.norm_apply(params["final_ln"], x, cfg)
    logits = L.unembed_apply(params["embed"], x, cfg, plan)
    return logits, cache


# =============================================================================
# decode step
# =============================================================================

def _split_experts(stack):
    """(the layer stack's params without its routed experts' weights, those
    weights stacked over the layers or None): the scan hands each layer its
    slice of the rest, and the expert layer the whole stack of experts
    (:func:`moe_lib.moe_dropless`)."""
    if "moe" not in stack:
        return stack, None
    moe = dict(stack["moe"])
    experts = {n: moe.pop(n) for n in ("wg", "wu", "wd")}
    return {**stack, "moe": moe}, experts


def paged_decode_applies(cfg: ModelConfig) -> bool:
    """True when every cache of the stack holds full-length attention
    entries (GQA K/V or MLA latents; dense prefix blocks included, no
    sliding window), so :func:`lm_decode` can run against a page pool."""
    return cfg.family in ("dense", "moe") and not cfg.sliding_window


def lm_decode(params, tokens, cache, pos, cfg: ModelConfig, plan: Plan,
              n_valid=None, bt=None):
    """tokens:(B,S) -> (logits (B,S,V), cache, stats); functional cache
    update.  ``stats`` sums the expert layers' counters over the layers
    (``moe.moe_dropless``; empty without experts).

    ``pos`` may be a scalar or a (B,) vector of per-slot positions, and S may
    exceed 1 (chunked-prefill extend, attention families); ``n_valid`` (B,)
    marks real tokens per row for ragged extends.

    With block tables ``bt`` (B, W), ``cache`` is a page pool shaped as
    :func:`lm_cache` builds it with pages on the batch axis (only where
    :func:`paged_decode_applies`): each layer writes its new entries into
    their pages and reads its own pages through ``bt``.  The stack's pool is
    carried through the layer scan, not returned as its ys (which would
    stack a copy), and comes back updated.
    """
    if bt is not None and not paged_decode_applies(cfg):
        raise ValueError("paged decode needs full-length GQA or MLA caches "
                         "(no sliding window or recurrent state)")
    x = L.embed_apply(params["embed"], tokens, cfg, plan)
    stats = {}

    if cfg.family in ("dense", "moe"):
        for i in range(cfg.first_k_dense):  # dense MLP blocks: no counters
            x, cache[f"dense{i}"], _ = attn_block_decode(
                params["blocks"][f"dense{i}"], x, cache[f"dense{i}"], pos, cfg,
                plan, n_valid=n_valid,
                pages=None if bt is None else (None, bt))

        stack, experts = _split_experts(params["blocks"]["stack"])
        n = jax.tree_util.tree_leaves(stack)[0].shape[0]
        layers = jnp.arange(n, dtype=jnp.int32)
        if bt is None:
            def body(x, pc):
                lp, lc = pc[:2]
                x, lc, st = attn_block_decode(
                    lp, x, lc, pos, cfg, plan, n_valid=n_valid,
                    experts=experts and (experts, pc[2]))
                return x, (lc, st)

            x, (new_stack, sts) = jax.lax.scan(
                body, x, (stack, cache["stack"])
                + ((layers,) if experts else ()))
        else:
            def body(carry, pl):
                x, pool = carry
                lp, layer = pl
                x, pool, st = attn_block_decode(
                    lp, x, pool, pos, cfg, plan, n_valid=n_valid,
                    pages=(layer, bt), experts=experts and (experts, layer))
                return (x, pool), st

            (x, new_stack), sts = jax.lax.scan(
                body, (x, cache["stack"]), (stack, layers))
        stats = {k: jnp.sum(v) for k, v in sts.items()}
        cache = {**cache, "stack": new_stack}
    elif cfg.family == "ssm":
        def body(x, pc):
            lp, lc = pc
            x, lc = ssm_block_decode(lp, x, lc, cfg, plan)
            return x, lc

        x, new_stack = jax.lax.scan(
            body, x, (params["blocks"]["stack"], cache["stack"]))
        cache = {**cache, "stack": new_stack}
    elif cfg.family == "hybrid":
        bp = params["blocks"]

        def group_body(x, pc):
            gp, gc, ac = pc

            def inner(x, plc):
                lp, lc = plc
                x, lc = ssm_block_decode(lp, x, lc, cfg, plan)
                return x, lc

            x, gc = jax.lax.scan(inner, x, (gp, gc))
            x, ac, _ = attn_block_decode(bp["shared_attn"], x, ac, pos, cfg,
                                         plan, n_valid=n_valid)
            return x, (gc, ac)

        x, (new_groups, new_attn) = jax.lax.scan(
            group_body, x, (bp["groups"], cache["groups"], cache["shared_attn"]))
        cache = {**cache, "groups": new_groups, "shared_attn": new_attn}
        if cache["tail"]:
            def inner(x, plc):
                lp, lc = plc
                x, lc = ssm_block_decode(lp, x, lc, cfg, plan)
                return x, lc
            x, new_tail = jax.lax.scan(inner, x, (bp["tail"], cache["tail"]))
            cache = {**cache, "tail": new_tail}

    x = L.norm_apply(params["final_ln"], x, cfg)
    logits = L.unembed_apply(params["embed"], x, cfg, plan)
    return logits, cache, stats

