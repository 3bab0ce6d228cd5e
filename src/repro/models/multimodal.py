"""VLM (llama-3.2-vision backbone) and Whisper (enc-dec) model definitions.

Modality frontends are STUBS per the assignment: ``input_specs()`` provides
precomputed patch/frame embeddings at model width; only the transformer
backbone is real. VLM: cross-attention block after every ``cross_attn_every``
self-attention layers (grouped scan). Whisper: 12L encoder (bidirectional) +
12L decoder with cross-attention, sinusoidal positions, unrolled (small model).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models.params import stack_tree
from repro.models.transformer import (
    ZERO_AUX, _maybe_remat, _seed_attn_cache, attn_block_apply,
    attn_block_decode, attn_block_params)
from repro.sharding.plan import Plan


# =============================================================================
# VLM: self-attn groups + gated cross-attn blocks
# =============================================================================

def cross_block_params(cfg: ModelConfig, plan: Plan):
    return {
        "ln1": L.norm_params(cfg),
        "attn": attn.gqa_params(cfg, plan, cross=True),
        "ln2": L.norm_params(cfg),
        "mlp": L.mlp_params(cfg),
    }


def cross_block_apply(p, x, img, cfg, plan, collect_kv=False):
    h = L.norm_apply(p["ln1"], x, cfg)
    a, kv = attn.gqa_apply(p["attn"], h, cfg, plan, kv_x=img, cross=True)
    x = x + a
    h = L.norm_apply(p["ln2"], x, cfg)
    x = x + L.mlp_apply(p["mlp"], h, cfg, plan)
    return (x, kv) if collect_kv else (x, None)


def cross_block_decode(p, x, kv_cache, cfg, plan):
    """Decode with frozen (prefill-computed) cross K/V."""
    h = L.norm_apply(p["ln1"], x, cfg)
    k, v = kv_cache["k"], kv_cache["v"]
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wq"].astype(dt))
    if cfg.qk_norm:
        q = L.rms_norm(q, p["attn"]["q_norm"], cfg.norm_eps)
    o = attn._sdpa(q, k, v, None, plan)
    o = jnp.einsum("bshd,hdk->bsk", o, p["attn"]["wo"].astype(dt))
    o = o * jnp.tanh(p["attn"]["gate"].astype(dt))
    x = x + o
    h = L.norm_apply(p["ln2"], x, cfg)
    return x + L.mlp_apply(p["mlp"], h, cfg, plan)


def vlm_params(cfg: ModelConfig, plan: Plan):
    k = cfg.cross_attn_every
    n_groups = cfg.num_layers // k
    return {
        "embed": L.embed_params(cfg, plan),
        "final_ln": L.norm_params(cfg),
        "blocks": {
            "groups": stack_tree(
                stack_tree(attn_block_params(cfg, plan, use_moe=False), k),
                n_groups),
            "cross": stack_tree(cross_block_params(cfg, plan), n_groups),
        },
    }


def vlm_apply(params, tokens, image_embeds, cfg: ModelConfig, plan: Plan):
    x = L.embed_apply(params["embed"], tokens, cfg, plan)
    img = image_embeds.astype(x.dtype)

    def group_body(carry, gp):
        x = carry
        sp, cp = gp

        def inner(c, lp):
            c, _ = attn_block_apply(lp, c, cfg, plan)
            return c, None

        x, _ = jax.lax.scan(inner, x, sp)
        x, _ = cross_block_apply(cp, x, img, cfg, plan)
        return x, None

    x, _ = jax.lax.scan(
        _maybe_remat(group_body, cfg), x,
        (params["blocks"]["groups"], params["blocks"]["cross"]))
    x = L.norm_apply(params["final_ln"], x, cfg)
    return L.unembed_apply(params["embed"], x, cfg, plan), ZERO_AUX()


def vlm_prefill(params, tokens, image_embeds, cfg, plan,
                max_len: Optional[int] = None, lengths=None):
    B, S = tokens.shape
    max_len = max_len or S
    dtype = L.cdt(cfg)
    x = L.embed_apply(params["embed"], tokens, cfg, plan)
    img = image_embeds.astype(x.dtype)

    def group_body(carry, gp):
        x = carry
        sp, cp = gp

        def inner(c, lp):
            c, _, kv = attn_block_apply(lp, c, cfg, plan, collect_kv=True)
            return c, kv

        x, kvs = jax.lax.scan(inner, x, sp)
        x, ckv = cross_block_apply(cp, x, img, cfg, plan, collect_kv=True)
        return x, (kvs, ckv)

    x, (kvs, ckvs) = jax.lax.scan(
        _maybe_remat(group_body, cfg), x,
        (params["blocks"]["groups"], params["blocks"]["cross"]))
    cache = {
        "self": jax.vmap(jax.vmap(
            lambda kv: _seed_attn_cache(cfg, plan, kv, max_len, dtype, B,
                                        lengths)))(kvs),
        "cross": {"k": ckvs[0], "v": ckvs[1]},
    }
    x = L.norm_apply(params["final_ln"], x, cfg)
    return L.unembed_apply(params["embed"], x, cfg, plan), cache


def vlm_cache(cfg, plan, batch, max_len, dtype, abstract=False):
    k = cfg.cross_attn_every
    n_groups = cfg.num_layers // k
    hkv, dh = plan.num_kv_heads, cfg.head_dim
    I = cfg.num_image_tokens

    def rep(tree, n):
        def do(leaf):
            if abstract:
                return jax.ShapeDtypeStruct((n,) + leaf.shape, leaf.dtype)
            return jnp.broadcast_to(leaf, (n,) + leaf.shape).copy()
        return jax.tree_util.tree_map(do, tree)

    a = (attn.gqa_cache_abstract(cfg, plan, batch, max_len, dtype) if abstract
         else attn.gqa_cache_init(cfg, plan, batch, max_len, dtype))
    mk = jax.ShapeDtypeStruct if abstract else (lambda s, d: jnp.zeros(s, d))
    return {
        "self": rep(rep(a, k), n_groups),
        "cross": {"k": mk((n_groups, batch, I, hkv, dh), dtype),
                  "v": mk((n_groups, batch, I, hkv, dh), dtype)},
    }


def vlm_cache_specs(cfg, plan, seq_axis=None):
    from jax.sharding import PartitionSpec as P
    a = attn.gqa_cache_spec(plan, seq_axis)

    def add(tree, n=1):
        for _ in range(n):
            tree = jax.tree_util.tree_map(
                lambda s: P(*((None,) + tuple(s))), tree,
                is_leaf=lambda x: isinstance(x, P))
        return tree

    kvh = plan.rules.get("kv_heads")
    return {
        "self": add(a, 2),
        "cross": {"k": P(None, plan.batch_axes, None, kvh, None),
                  "v": P(None, plan.batch_axes, None, kvh, None)},
    }


def vlm_decode(params, tokens, cache, pos, cfg: ModelConfig, plan: Plan,
               n_valid=None):
    x = L.embed_apply(params["embed"], tokens, cfg, plan)

    def group_body(x, pc):
        (sp, cp), (sc, cc) = pc

        def inner(x, plc):
            lp, lc = plc
            x, lc, _ = attn_block_decode(lp, x, lc, pos, cfg, plan,
                                         n_valid=n_valid)
            return x, lc

        x, sc = jax.lax.scan(inner, x, (sp, sc))
        x = cross_block_decode(cp, x, cc, cfg, plan)
        return x, (sc, cc)

    x, (new_self, _) = jax.lax.scan(
        group_body, x,
        ((params["blocks"]["groups"], params["blocks"]["cross"]),
         (cache["self"], cache["cross"])))
    cache = {**cache, "self": new_self}
    x = L.norm_apply(params["final_ln"], x, cfg)
    return L.unembed_apply(params["embed"], x, cfg, plan), cache


# =============================================================================
# Whisper: encoder-decoder
# =============================================================================

def sinusoidal(S: int, d: int, dtype):
    pos = jnp.arange(S)[:, None].astype(jnp.float32)
    i = jnp.arange(d // 2)[None].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)


def dec_block_params(cfg, plan):
    return {
        "ln1": L.norm_params(cfg),
        "self_attn": attn.gqa_params(cfg, plan),
        "ln_x": L.norm_params(cfg),
        "cross_attn": attn.gqa_params(cfg, plan, cross=True),
        "ln2": L.norm_params(cfg),
        "mlp": L.mlp_params(cfg),
    }


def whisper_params(cfg: ModelConfig, plan: Plan):
    enc_block = {"ln1": L.norm_params(cfg), "attn": attn.gqa_params(cfg, plan),
                 "ln2": L.norm_params(cfg), "mlp": L.mlp_params(cfg)}
    return {
        "embed": L.embed_params(cfg, plan),
        "enc": stack_tree(enc_block, cfg.encoder_layers),
        "enc_ln": L.norm_params(cfg),
        "dec": stack_tree(dec_block_params(cfg, plan), cfg.num_layers),
        "final_ln": L.norm_params(cfg),
    }


def whisper_encode(params, frames, cfg, plan):
    """frames: (B, F, d_model) precomputed (conv frontend stub)."""
    x = frames.astype(L.cdt(cfg))
    x = x + sinusoidal(x.shape[1], cfg.d_model, x.dtype)[None]

    def body(x, lp):
        h = L.norm_apply(lp["ln1"], x, cfg)
        a, _ = attn.gqa_apply(lp["attn"], h, cfg, plan, causal=False)
        x = x + a
        h = L.norm_apply(lp["ln2"], x, cfg)
        return x + L.mlp_apply(lp["mlp"], h, cfg, plan), None

    x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, params["enc"])
    return L.norm_apply(params["enc_ln"], x, cfg)


def _dec_block(lp, x, enc_out, cfg, plan, positions=None, collect_kv=False):
    h = L.norm_apply(lp["ln1"], x, cfg)
    a, kv = attn.gqa_apply(lp["self_attn"], h, cfg, plan, positions=positions)
    x = x + a
    h = L.norm_apply(lp["ln_x"], x, cfg)
    a, ckv = attn.gqa_apply(lp["cross_attn"], h, cfg, plan, kv_x=enc_out,
                            cross=True)
    x = x + a
    h = L.norm_apply(lp["ln2"], x, cfg)
    x = x + L.mlp_apply(lp["mlp"], h, cfg, plan)
    return (x, kv, ckv) if collect_kv else (x, None, None)


def whisper_apply(params, tokens, frames, cfg: ModelConfig, plan: Plan):
    enc_out = whisper_encode(params, frames, cfg, plan)
    x = L.embed_apply(params["embed"], tokens, cfg, plan)
    x = x + sinusoidal(x.shape[1], cfg.d_model, x.dtype)[None]

    def body(x, lp):
        x, _, _ = _dec_block(lp, x, enc_out, cfg, plan)
        return x, None

    x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, params["dec"])
    x = L.norm_apply(params["final_ln"], x, cfg)
    return L.unembed_apply(params["embed"], x, cfg, plan), ZERO_AUX()


def whisper_prefill(params, tokens, frames, cfg, plan,
                    max_len: Optional[int] = None, lengths=None):
    B, S = tokens.shape
    max_len = max_len or S
    dtype = L.cdt(cfg)
    enc_out = whisper_encode(params, frames, cfg, plan)
    x = L.embed_apply(params["embed"], tokens, cfg, plan)
    x = x + sinusoidal(S, cfg.d_model, x.dtype)[None]

    def body(x, lp):
        x, kv, ckv = _dec_block(lp, x, enc_out, cfg, plan, collect_kv=True)
        return x, (kv, ckv)

    x, (kvs, ckvs) = jax.lax.scan(_maybe_remat(body, cfg), x, params["dec"])
    cache = {
        "self": jax.vmap(
            lambda kv: _seed_attn_cache(cfg, plan, kv, max_len, dtype, B,
                                        lengths))(kvs),
        "cross": {"k": ckvs[0], "v": ckvs[1]},
    }
    x = L.norm_apply(params["final_ln"], x, cfg)
    return L.unembed_apply(params["embed"], x, cfg, plan), cache


def whisper_cache(cfg, plan, batch, max_len, dtype, abstract=False):
    hkv, dh = plan.num_kv_heads, cfg.head_dim
    F = cfg.encoder_frames
    nl = cfg.num_layers

    def rep(tree, n):
        def do(leaf):
            if abstract:
                return jax.ShapeDtypeStruct((n,) + leaf.shape, leaf.dtype)
            return jnp.broadcast_to(leaf, (n,) + leaf.shape).copy()
        return jax.tree_util.tree_map(do, tree)

    a = (attn.gqa_cache_abstract(cfg, plan, batch, max_len, dtype) if abstract
         else attn.gqa_cache_init(cfg, plan, batch, max_len, dtype))
    mk = jax.ShapeDtypeStruct if abstract else (lambda s, d: jnp.zeros(s, d))
    return {
        "self": rep(a, nl),
        "cross": {"k": mk((nl, batch, F, hkv, dh), dtype),
                  "v": mk((nl, batch, F, hkv, dh), dtype)},
    }


def whisper_cache_specs(cfg, plan, seq_axis=None):
    from jax.sharding import PartitionSpec as P
    a = attn.gqa_cache_spec(plan, seq_axis)
    add = lambda tree: jax.tree_util.tree_map(
        lambda s: P(*((None,) + tuple(s))), tree,
        is_leaf=lambda x: isinstance(x, P))
    kvh = plan.rules.get("kv_heads")
    return {
        "self": add(a),
        "cross": {"k": P(None, plan.batch_axes, None, kvh, None),
                  "v": P(None, plan.batch_axes, None, kvh, None)},
    }


def whisper_decode(params, tokens, cache, pos, cfg: ModelConfig, plan: Plan,
                   n_valid=None):
    B, S = tokens.shape
    x = L.embed_apply(params["embed"], tokens, cfg, plan)
    x = x + _sin_at(attn.decode_positions(pos, B, S), cfg, x.dtype)

    def body(x, pc):
        lp, (sc, cc) = pc
        h = L.norm_apply(lp["ln1"], x, cfg)
        a, sc = attn.gqa_decode(lp["self_attn"], h, sc, pos, cfg, plan,
                                n_valid=n_valid)
        x = x + a
        h = L.norm_apply(lp["ln_x"], x, cfg)
        dt = x.dtype
        q = jnp.einsum("bsd,dhk->bshk", h, lp["cross_attn"]["wq"].astype(dt))
        o = attn._sdpa(q, cc["k"], cc["v"], None, plan)
        o = jnp.einsum("bshd,hdk->bsk", o, lp["cross_attn"]["wo"].astype(dt))
        x = x + o * jnp.tanh(lp["cross_attn"]["gate"].astype(dt))
        h = L.norm_apply(lp["ln2"], x, cfg)
        x = x + L.mlp_apply(lp["mlp"], h, cfg, plan)
        return x, sc

    x, new_self = jax.lax.scan(
        body, x, (params["dec"], (cache["self"], cache["cross"])))
    cache = {**cache, "self": new_self}
    x = L.norm_apply(params["final_ln"], x, cfg)
    return L.unembed_apply(params["embed"], x, cfg, plan), cache


def _sin_at(positions, cfg, dtype):
    """Sinusoidal embedding at absolute ``positions`` (B,S) -> (B,S,d)."""
    d = cfg.d_model
    i = jnp.arange(d // 2).astype(jnp.float32)
    ang = jnp.asarray(positions, jnp.float32)[..., None] / \
        jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)
