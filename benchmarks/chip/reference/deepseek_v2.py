"""Plain float32 DeepSeek-V2 decoder, written from the published description.

DeepSeek-V2 (deepseek-ai/DeepSeek-V2 ``config.json``, its modelling code and
arXiv:2405.04434): token embedding; per layer, pre-RMSNorm, multi-head latent
attention, a residual, pre-RMSNorm, an MLP and a residual; a final RMSNorm;
logits against an untied output head.

- Latent attention, expanded per head: the query is ``q_b(norm(q_a x))``,
  per head ``qk_nope_head_dim`` values without rotary embedding and
  ``qk_rope_head_dim`` with it; ``kv_a x`` gives a ``kv_lora_rank`` latent
  (RMS-normalised) and one rotary key shared by every head; the per-head key
  is ``k_b(latent)`` joined to the shared rotary key, the value
  ``v_b(latent)``.  Softmax scale ``q_head_dim ** -0.5 * m ** 2``.
- Rotary embedding YaRN: frequencies blended between interpolated
  (divided by ``factor``) and kept by a linear ramp over the dimensions
  whose rotations over the original context fall between ``beta_slow`` and
  ``beta_fast``; ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; cos and sin
  carry ``mscale / mscale_all_dim`` of the same form (1 here).
- The first ``first_k_dense_replace`` layers have a SwiGLU MLP of
  ``intermediate_size``; the others an expert layer: softmax scores of all
  the published experts from float32 logits, the ``topk_group`` of
  ``n_group`` groups with the highest best score kept, the top
  ``num_experts_per_tok`` inside them, weighted by their scores times
  ``routed_scaling_factor`` (``norm_topk_prob`` false), each expert a SwiGLU
  of ``moe_intermediate_size``; plus ``n_shared_experts`` shared experts as
  one SwiGLU of that many times the width, for every token.

Departures, none of which changes the mathematics with random weights:

- The deployment's share (``deployment`` in the configuration): routing runs
  over all ``deployment.experts_published`` experts, but only the
  ``n_routed_experts`` held from ``deployment.held_expert_start`` have
  weights, and the expert layer is their dense sum over every token,
  weighted by the router (zero for a token that did not pick them); what
  the absent experts would add is left out, as in the program.
- Rotary dimensions are paired as halves (``x[:d/2]``, ``x[d/2:]``), not
  interleaved; with random weights this only relabels dimensions.
- ``kv_b_proj`` is held as its two halves, ``k_b_proj`` and ``v_b_proj``;
  ``lm_head`` as (hidden, vocab).
- Weights are random (from the benchmark's seed); sequences are one at a
  time, right-padded to a fixed length so one program serves them all.

The weights ``w`` may be held in the served dtype: each layer's are upcast
to float32 as the layer runs (one layer's float32 copy live at a time), the
attention runs over a block of heads at a time and the output head over a
block of the vocabulary at a time, so the whole pass fits on one chip.

Every matrix product goes through ``mm``: ``exact`` is float32 at
``highest`` precision, the reference; ``fp8`` rounds both operands to
float8_e4m3fn (per-tensor scale) before an exact product, the control that
stands for computing below the served bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HEAD_BLOCK = 16      # attention heads per block
VOCAB_BLOCKS = 8     # blocks of the output head


def _attn_specs(src: dict, lead: tuple) -> dict:
    D, H = src["hidden_size"], src["num_attention_heads"]
    qr, r = src["q_lora_rank"], src["kv_lora_rank"]
    nope, rope, vd = (src["qk_nope_head_dim"], src["qk_rope_head_dim"],
                      src["v_head_dim"])
    nrm = ("norm", 0.1)
    return {
        "input_layernorm": (lead + (D,), nrm),
        "post_attention_layernorm": (lead + (D,), nrm),
        "q_a_proj": (lead + (D, qr), ("normal", D ** -0.5)),
        "q_a_layernorm": (lead + (qr,), nrm),
        "q_b_proj": (lead + (qr, H * (nope + rope)), ("normal", qr ** -0.5)),
        "kv_a_proj_with_mqa": (lead + (D, r + rope), ("normal", D ** -0.5)),
        "kv_a_layernorm": (lead + (r,), nrm),
        "k_b_proj": (lead + (r, H * nope), ("normal", r ** -0.5)),
        "v_b_proj": (lead + (r, H * vd), ("normal", r ** -0.5)),
        "o_proj": (lead + (H * vd, D), ("normal", (H * vd) ** -0.5)),
    }


def _mlp_specs(D: int, F: int, lead: tuple) -> dict:
    return {"gate_proj": (lead + (D, F), ("normal", D ** -0.5)),
            "up_proj": (lead + (D, F), ("normal", D ** -0.5)),
            "down_proj": (lead + (F, D), ("normal", F ** -0.5))}


def param_specs(src: dict) -> dict:
    """name -> (shape, init); init is ("normal", std) or ("norm", spread).
    The dense layers' weights are named ``dense.*`` and the expert layers'
    ``moe.*``, each stacked on a leading layer axis where there are more
    than one (the dense layer of DeepSeek-V2 is one)."""
    D, V = src["hidden_size"], src["vocab_size"]
    n_dense = src["first_k_dense_replace"]
    n_moe = src["num_hidden_layers"] - n_dense
    E, f = src["n_routed_experts"], src["moe_intermediate_size"]
    lead_d = () if n_dense == 1 else (n_dense,)
    specs = {"embed_tokens": ((V, D), ("normal", D ** -0.5)),
             "norm": ((D,), ("norm", 0.1)),
             "lm_head": ((D, V), ("normal", D ** -0.5))}
    parts = {**_attn_specs(src, lead_d),
             **_mlp_specs(D, src["intermediate_size"], lead_d)}
    specs.update({f"dense.{k}": v for k, v in parts.items()})
    parts = {**_attn_specs(src, (n_moe,)),
             "gate": ((n_moe, D, src["deployment"]["experts_published"]),
                      ("normal", D ** -0.5))}
    parts.update({f"experts.{k}": ((n_moe, E) + s, i)
                  for k, (s, i) in _mlp_specs(D, f, ()).items()})
    parts.update({f"shared_experts.{k}": v for k, v in _mlp_specs(
        D, src["n_shared_experts"] * f, (n_moe,)).items()})
    specs.update({f"moe.{k}": v for k, v in parts.items()})
    return specs


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


def _yarn_m(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(src: dict):
    """(inverse frequencies (rope/2,), cos/sin factor, softmax scale)."""
    d, base = src["qk_rope_head_dim"], src["rope_theta"]
    y = src["rope_scaling"]
    factor, orig = y["factor"], y["original_max_position_embeddings"]
    extra = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    inter = 1.0 / (factor * base ** (jnp.arange(0, d, 2, dtype=jnp.float32)
                                     / d))

    def dim_at(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_at(y["beta_fast"])), 0)
    high = min(math.ceil(dim_at(y["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv = inter * ramp + extra * (1.0 - ramp)
    cs = (_yarn_m(factor, y["mscale"]) / _yarn_m(factor, y["mscale_all_dim"]))
    m = _yarn_m(factor, y["mscale_all_dim"])
    q_dim = src["qk_nope_head_dim"] + d
    return inv, cs, q_dim ** -0.5 * m * m


def _rope(x, pos, inv, cs):
    d = x.shape[-1]
    ang = pos[:, None].astype(jnp.float32) * inv             # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None] * cs, jnp.sin(ang)[:, None] * cs
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, h, pos, src, mm, rope):
    S = h.shape[0]
    H, eps = src["num_attention_heads"], src["rms_norm_eps"]
    nope, rd, vd = (src["qk_nope_head_dim"], src["qk_rope_head_dim"],
                    src["v_head_dim"])
    r = src["kv_lora_rank"]
    inv, cs, scale = rope
    q = mm(_rms(mm(h, p["q_a_proj"], "sd,dq->sq"), p["q_a_layernorm"], eps),
           p["q_b_proj"], "sq,qe->se").reshape(S, H, nope + rd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv, cs)],
                        -1)
    kv = mm(h, p["kv_a_proj_with_mqa"], "sd,de->se")
    c = _rms(kv[:, :r], p["kv_a_layernorm"], eps)
    k_rope = _rope(kv[:, None, r:], pos, inv, cs)[:, 0]       # (S, rope)
    causal = pos[:, None] >= pos[None, :]
    hb = min(HEAD_BLOCK, H)
    nb = H // hb
    k_b = p["k_b_proj"].reshape(r, nb, hb, nope)
    v_b = p["v_b_proj"].reshape(r, nb, hb, vd)
    qb = q.reshape(S, nb, hb, nope + rd)

    def heads(j):
        k = mm(c, k_b[:, j], "sr,rhk->shk")
        k = jnp.concatenate([k, jnp.broadcast_to(
            k_rope[:, None], (S, hb, rd))], -1)
        v = mm(c, v_b[:, j], "sr,rhk->shk")
        s = mm(qb[:, j], k, "shd,thd->hst") * scale
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm(a, v, "hst,thd->shd")                      # (S, hb, vd)

    o = jax.lax.map(heads, jnp.arange(nb))                   # (nb, S, hb, vd)
    o = jnp.moveaxis(o, 0, 1).reshape(S, H * vd)
    return mm(o, p["o_proj"], "se,ed->sd")


def _swiglu(p, h, mm, spec_in="sd,df->sf", spec_out="sf,fd->sd"):
    g = mm(h, p["gate_proj"], spec_in)
    u = mm(h, p["up_proj"], spec_in)
    return mm(jax.nn.silu(g) * u, p["down_proj"], spec_out)


def route(p, h, src):
    """(weights (S, experts_published), picks (S, k)) of the gate: zero
    weight for every expert a token did not pick."""
    logits = exact(h, p["gate"], "sd,de->se")   # float32, as published
    probs = jax.nn.softmax(logits, axis=-1)
    S, E = probs.shape
    G, keep = src["n_group"], src["topk_group"]
    best = jnp.max(probs.reshape(S, G, E // G), -1)
    _, gi = jax.lax.top_k(best, keep)
    kept = jnp.zeros((S, G), bool).at[jnp.arange(S)[:, None], gi].set(True)
    masked = jnp.where(jnp.repeat(kept, E // G, axis=-1), probs, 0.0)
    w, idx = jax.lax.top_k(masked, src["num_experts_per_tok"])
    w = w * src["routed_scaling_factor"]
    dense_w = jnp.zeros_like(probs).at[jnp.arange(S)[:, None], idx].add(w)
    return dense_w, idx


def _experts(p, h, src, mm):
    weight, idx = route(p, h, src)
    first = src["deployment"]["held_expert_start"]
    held = weight[:, first:first + src["n_routed_experts"]]   # (S, E_held)
    ex = {k[len("experts."):]: v for k, v in p.items()
          if k.startswith("experts.")}
    y = _swiglu(ex, h, mm, "sd,edf->sef", "sef,efd->sed")    # (S, E_held, D)
    shared = _swiglu({k[len("shared_experts."):]: v for k, v in p.items()
                      if k.startswith("shared_experts.")}, h, mm)
    return jnp.einsum("se,sed->sd", held, y,
                      precision=jax.lax.Precision.HIGHEST) + shared, idx


def _layer(x, p, pos, src, mm, rope, moe):
    """(x after the layer, the gate's picks (S, k) or None)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    eps = src["rms_norm_eps"]
    x = x + _attention(p, _rms(x, p["input_layernorm"], eps), pos, src, mm,
                       rope)
    h = _rms(x, p["post_attention_layernorm"], eps)
    if not moe:
        return x + _swiglu(p, h, mm), None
    y, idx = _experts(p, h, src, mm)
    return x + y, idx


def _group(w, prefix):
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


@functools.partial(jax.jit, static_argnames=("src_key", "mm"))
def _forward(w, tokens, src_key, mm):
    src = dict(src_key)
    src["deployment"] = dict(src["deployment"])
    src["rope_scaling"] = dict(src["rope_scaling"])
    S = tokens.shape[0]
    pos = jnp.arange(S)
    rope = yarn(src)
    x = w["embed_tokens"][tokens].astype(jnp.float32)
    dense = _group(w, "dense.")
    if src["first_k_dense_replace"] == 1:
        x, _ = _layer(x, dense, pos, src, mm, rope, moe=False)
    else:
        x, _ = jax.lax.scan(lambda x, p: (_layer(x, p, pos, src, mm, rope,
                                                 False)[0], None), x, dense)
    x, picks = jax.lax.scan(lambda x, p: _layer(x, p, pos, src, mm, rope,
                                                True), x, _group(w, "moe."))
    x = _rms(x, w["norm"].astype(jnp.float32), src["rms_norm_eps"])
    D, V = w["lm_head"].shape
    blk = V // VOCAB_BLOCKS

    def vocab(j):
        head = jax.lax.dynamic_slice(w["lm_head"], (0, j * blk), (D, blk))
        return mm(x, head.astype(jnp.float32), "sd,dv->sv")

    out = jax.lax.map(vocab, jnp.arange(VOCAB_BLOCKS))         # (nb, S, blk)
    return jnp.moveaxis(out, 0, 1).reshape(S, V), picks


def _key(src: dict):
    def plain(d):
        return tuple(sorted((k, v) for k, v in d.items()
                            if isinstance(v, (int, float, bool, str))))
    return plain(src) + (("deployment", plain(src["deployment"])),
                         ("rope_scaling", plain(src["rope_scaling"])))


def logits(w, tokens, src: dict, mm=exact):
    """(S,) int32 -> (S, V) float32 next-token logits."""
    with jax.default_matmul_precision("highest"):
        return _forward(w, jnp.asarray(tokens, jnp.int32), _key(src), mm)[0]


def routes(w, tokens, src: dict):
    """(S,) int32 -> (expert layers, S, k): each expert layer's picks per
    token, among all the published experts."""
    with jax.default_matmul_precision("highest"):
        return _forward(w, jnp.asarray(tokens, jnp.int32), _key(src),
                        exact)[1]
