"""Plain float32 Qwen3 decoder, written from the published description.

Qwen3 (Qwen/Qwen3-1.7B ``config.json``, model card and the Qwen3 technical
report): token embedding; per layer, pre-RMSNorm, GQA attention whose
per-head query and key vectors are RMS-normalised over the head dimension
before rotary embedding (the halves convention, theta ``rope_theta``), a
residual, pre-RMSNorm, a SwiGLU MLP (``down(silu(gate x) * up x)``) and a
residual; a final RMSNorm; logits against the tied embedding.  No biases.

Departures: none in the mathematics.  Weights are random (from the
benchmark's seed), so nothing here loads a checkpoint; sequences are one at
a time, padded on the right to a fixed length so one program serves them
all (causal attention never lets padding reach an earlier position).

Every matrix product goes through ``mm``: ``exact`` is float32 at
``highest`` precision, the reference; ``fp8`` rounds both operands to
float8_e4m3fn (per-tensor scale) before an exact product, the control that
stands for computing below the served bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NAMES = ("embed_tokens", "norm", "input_layernorm", "post_attention_layernorm",
         "q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm",
         "gate_proj", "up_proj", "down_proj")


def param_specs(src: dict) -> dict:
    """name -> (shape, init); init is ("normal", std) or ("norm", spread).
    Per-layer weights are stacked on a leading layer axis."""
    L, D = src["num_hidden_layers"], src["hidden_size"]
    H, Hkv = src["num_attention_heads"], src["num_key_value_heads"]
    dh = src.get("head_dim") or D // H
    F, V = src["intermediate_size"], src["vocab_size"]
    assert src.get("tie_word_embeddings"), "untied heads are not written here"
    nrm = ("norm", 0.1)
    return {
        "embed_tokens": ((V, D), ("normal", D ** -0.5)),
        "norm": ((D,), nrm),
        "input_layernorm": ((L, D), nrm),
        "post_attention_layernorm": ((L, D), nrm),
        "q_proj": ((L, D, H * dh), ("normal", D ** -0.5)),
        "k_proj": ((L, D, Hkv * dh), ("normal", D ** -0.5)),
        "v_proj": ((L, D, Hkv * dh), ("normal", D ** -0.5)),
        "o_proj": ((L, H * dh, D), ("normal", (H * dh) ** -0.5)),
        "q_norm": ((L, dh), nrm),
        "k_norm": ((L, dh), nrm),
        "gate_proj": ((L, D, F), ("normal", D ** -0.5)),
        "up_proj": ((L, D, F), ("normal", D ** -0.5)),
        "down_proj": ((L, F, D), ("normal", F ** -0.5)),
    }


def exact(a, b, spec):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def fp8(a, b, spec):
    return exact(_fp8(a), _fp8(b), spec)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]  # (S, 1, d/2)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("src_key", "mm"))
def _forward(w, tokens, src_key, mm):
    src = dict(src_key)
    S = tokens.shape[0]
    D, H = src["hidden_size"], src["num_attention_heads"]
    Hkv = src["num_key_value_heads"]
    dh = src.get("head_dim") or D // H
    G, eps, theta = H // Hkv, src["rms_norm_eps"], src["rope_theta"]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    x = w["embed_tokens"][tokens]

    def layer(x, p):
        h = _rms(x, p["input_layernorm"], eps)
        q = mm(h, p["q_proj"], "sd,de->se").reshape(S, H, dh)
        k = mm(h, p["k_proj"], "sd,de->se").reshape(S, Hkv, dh)
        v = mm(h, p["v_proj"], "sd,de->se").reshape(S, Hkv, dh)
        q = _rope(_rms(q, p["q_norm"], eps), pos, theta)
        k = _rope(_rms(k, p["k_norm"], eps), pos, theta)
        q = q.reshape(S, Hkv, G, dh)
        s = mm(q, k, "skgd,tkd->kgst") / jnp.sqrt(jnp.float32(dh))
        s = jnp.where(causal, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = mm(a, v, "kgst,tkd->skgd").reshape(S, H * dh)
        x = x + mm(o, p["o_proj"], "se,ed->sd")
        h = _rms(x, p["post_attention_layernorm"], eps)
        g = mm(h, p["gate_proj"], "sd,df->sf")
        u = mm(h, p["up_proj"], "sd,df->sf")
        x = x + mm(jax.nn.silu(g) * u, p["down_proj"], "sf,fd->sd")
        return x, None

    stacked = {k: v for k, v in w.items() if k not in ("embed_tokens", "norm")}
    x, _ = jax.lax.scan(layer, x, stacked)
    x = _rms(x, w["norm"], eps)
    return mm(x, w["embed_tokens"], "sd,vd->sv")


def logits(w, tokens, src: dict, mm=exact):
    """(S,) int32 -> (S, V) float32 next-token logits."""
    key = tuple(sorted((k, v) for k, v in src.items()
                       if isinstance(v, (int, float, bool, str))))
    with jax.default_matmul_precision("highest"):
        return _forward(w, jnp.asarray(tokens, jnp.int32), key, mm)


def loss(w, tokens, labels, src: dict, z_coef: float = 0.0, mm=exact):
    """Mean next-token cross-entropy over a batch (B, S) of sequences, plus
    ``z_coef`` x mean(logsumexp^2) where a trainer adds a z-loss."""
    lg = jax.vmap(lambda t: logits(w, t, src, mm))(tokens)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold) + z_coef * jnp.mean(lse * lse)


def grad(w, tokens, labels, src: dict, z_coef: float = 0.0):
    """(loss, gradients by weight name)."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(w, tokens, labels, src, z_coef)
