"""Core layers: norms, MLPs, embeddings, rotary position embedding.

Functional style: ``*_params(cfg, plan)`` builds a ParamMeta tree,
``*_apply(p, x, ...)`` runs the layer. Compute dtype is bf16 (cast at use);
parameters are stored in ``cfg.param_dtype``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamMeta, dense
from repro.sharding.plan import Plan


def cdt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# --- dense-matmul routing hook ------------------------------------------------

#: optional override for the MXU-dominant dense matmuls (the MLP blocks):
#: a callable ``(x_2d_f32, w_2d_f32) -> y_2d_f32``.  None keeps the plain
#: ``@``.  Set via ``repro.tolerance.abft.routed_matmuls`` to run a model
#: through the ABFT-checksummed over-scaled matmul; the override executes
#: host-side state (SDC counters), so route only non-jitted evaluation.
MATMUL = None


def matmul(x, w):
    """x: (..., K) @ w: (K, N), through the routing hook when installed."""
    if MATMUL is None:
        return x @ w
    y = MATMUL(x.reshape(-1, x.shape[-1]).astype(jnp.float32),
               w.astype(jnp.float32))
    return y.reshape(*x.shape[:-1], w.shape[-1]).astype(x.dtype)


# --- norms -------------------------------------------------------------------

def norm_params(cfg: ModelConfig, dim: Optional[int] = None, logical="embed"):
    d = dim or cfg.d_model
    p = {"scale": ParamMeta((d,), (logical,), init="ones")}
    if cfg.norm_type == "layernorm":
        p["bias"] = ParamMeta((d,), (logical,), init="zeros")
    return p


def norm_apply(p, x, cfg: ModelConfig):
    dt = x.dtype
    x = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + cfg.norm_eps)
    x = x * p["scale"].astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        x = x + p["bias"].astype(jnp.float32)
    return x.astype(dt)


def rms_norm(x, scale, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


# --- MLP ---------------------------------------------------------------------

def mlp_params(cfg: ModelConfig, d_ff: Optional[int] = None, ffn_logical="ffn"):
    ff = d_ff or cfg.d_ff
    d = cfg.d_model
    p = {"wd": dense(ff, d, ffn_logical, "embed")}
    if cfg.mlp_type == "swiglu":
        p["wg"] = dense(d, ff, "embed", ffn_logical)
        p["wu"] = dense(d, ff, "embed", ffn_logical)
    else:  # relu2 | gelu
        p["wu"] = dense(d, ff, "embed", ffn_logical)
    return p


def mlp_apply(p, x, cfg: ModelConfig, plan: Plan):
    dt = x.dtype
    if cfg.mlp_type == "swiglu":
        g = matmul(x, p["wg"].astype(dt))
        u = matmul(x, p["wu"].astype(dt))
        h = jax.nn.silu(g) * u
    elif cfg.mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(matmul(x, p["wu"].astype(dt))))
    else:
        h = jax.nn.gelu(matmul(x, p["wu"].astype(dt)))
    h = plan.act(h, "batch", None, "ffn")
    return matmul(h, p["wd"].astype(dt))


# --- embeddings ----------------------------------------------------------------

def embed_params(cfg: ModelConfig, plan: Plan):
    p = {"embedding": ParamMeta((plan.vocab, cfg.d_model), ("vocab", "embed"),
                                init="embed", fan_in=cfg.d_model)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense(cfg.d_model, plan.vocab, "embed", "vocab")
    return p


def embed_apply(p, tokens, cfg: ModelConfig, plan: Plan):
    x = jnp.take(p["embedding"].astype(cdt(cfg)), tokens, axis=0)
    return plan.act(x, "batch", "seq", None)


def unembed_apply(p, x, cfg: ModelConfig, plan: Plan):
    dt = x.dtype
    if cfg.tie_embeddings:
        logits = x @ p["embedding"].astype(dt).T
    else:
        logits = x @ p["unembed"].astype(dt)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return plan.act(logits, "batch", None, "vocab")


# --- rotary -------------------------------------------------------------------

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_attn_mscale(cfg: ModelConfig) -> float:
    """YaRN's ``m``: the softmax scale of an attention with YaRN rope is
    ``q_head_dim ** -0.5 * m ** 2`` (DeepSeek-V2's modelling code; 1 without
    YaRN or without ``mscale_all_dim``)."""
    y = cfg.yarn
    if not y or not y.get("mscale_all_dim"):
        return 1.0
    return _yarn_mscale(y["factor"], y["mscale_all_dim"])


def rope_freqs(cfg: ModelConfig, dim: Optional[int] = None):
    d = dim or cfg.head_dim
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    y = cfg.yarn
    if y is None:
        return inv  # (d/2,)
    # YaRN: frequencies that turn fewer than beta_slow times over the
    # original context are interpolated (divided by the factor), those that
    # turn more than beta_fast times are kept, and a linear ramp over the
    # dimensions between blends the two
    orig, base = y["original_max_position_embeddings"], cfg.rope_theta

    def dim_at(rotations):
        return (d * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_at(y["beta_fast"])), 0)
    high = min(math.ceil(dim_at(y["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    return inv / y["factor"] * ramp + inv * (1.0 - ramp)


def apply_rope(x, positions, cfg: ModelConfig, dim: Optional[int] = None):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    if not cfg.use_rope:
        return x
    d = dim or x.shape[-1]
    inv = rope_freqs(cfg, d)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, d/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    y = cfg.yarn
    if y is not None:  # cos and sin carry mscale / mscale_all_dim
        ratio = (_yarn_mscale(y["factor"], y.get("mscale", 1.0))
                 / _yarn_mscale(y["factor"], y.get("mscale_all_dim", 0.0)))
        if ratio != 1.0:
            cos, sin = cos * ratio, sin * ratio
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
