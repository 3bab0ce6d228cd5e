"""Operations and bytes of a decoder with latent attention (MLA) and expert
layers of which this chip holds a share (DeepSeek-V2), computed from its
configuration's published sizes (its source keys and ``deployment``),
independent of any implementation; and the expert counters that the
engine's in-pool step reports, read from a traced tick.

FLOPs are model FLOPs, 2 per multiply-add: each token through every weight
it multiplies (the latent's up-projections ``k_b``/``v_b`` as weights of the
token's own latent), attention over its context as scores and weighted sum
of expanded heads, and the expert layers' routed part only for the picks
that land on experts this chip holds.
"""
from __future__ import annotations

from benchmarks.chip.counts import DTYPE_BYTES


def _sizes(src: dict) -> dict:
    return {"D": src["hidden_size"], "H": src["num_attention_heads"],
            "qr": src["q_lora_rank"], "r": src["kv_lora_rank"],
            "nope": src["qk_nope_head_dim"], "rope": src["qk_rope_head_dim"],
            "vd": src["v_head_dim"], "F": src["intermediate_size"],
            "f": src["moe_intermediate_size"], "V": src["vocab_size"],
            "k": src["num_experts_per_tok"], "held": src["n_routed_experts"],
            "published": src["deployment"]["experts_published"],
            "shared": src["n_shared_experts"],
            "dense": src["first_k_dense_replace"],
            "moe": src["num_hidden_layers"] - src["first_k_dense_replace"]}


def param_counts(src: dict) -> dict:
    """Parameters by part: ``attn`` (one layer's attention with its
    norms), ``layer_norms`` (a layer's two), ``dense_mlp``, ``shared`` and
    ``router`` (one expert layer's), ``expert`` (one routed expert),
    ``embed``, ``head`` and ``total`` (each stored parameter once)."""
    s = _sizes(src)
    D, H, qr, r = s["D"], s["H"], s["qr"], s["r"]
    attn = (D * qr + qr + qr * H * (s["nope"] + s["rope"])
            + D * (r + s["rope"]) + r + r * H * s["nope"] + r * H * s["vd"]
            + H * s["vd"] * D)
    c = {"attn": attn, "layer_norms": 2 * D, "dense_mlp": 3 * D * s["F"],
         "shared": 3 * D * s["shared"] * s["f"], "router": D * s["published"],
         "expert": 3 * D * s["f"], "embed": s["V"] * D, "head": s["V"] * D}
    layers = s["dense"] + s["moe"]
    c["total"] = (layers * (attn + 2 * D) + s["dense"] * c["dense_mlp"]
                  + s["moe"] * (c["shared"] + c["router"]
                                + s["held"] * c["expert"])
                  + c["embed"] + c["head"] + D)
    return c


def latent_bytes_per_token(src: dict, dtype: str = "bfloat16") -> int:
    """One token's cache entries over all layers: the latent and the shared
    rotary key of each."""
    s = _sizes(src)
    return ((s["dense"] + s["moe"]) * (s["r"] + s["rope"])
            * DTYPE_BYTES[dtype])


def token_flops(src: dict, context: int) -> float:
    """Forward FLOPs of one token at ``context`` (its own position
    included), without the routed experts."""
    s = _sizes(src)
    c = param_counts(src)
    matmul = ((s["dense"] + s["moe"]) * (c["attn"] - s["qr"] - s["r"])
              + s["dense"] * c["dense_mlp"]
              + s["moe"] * (c["shared"] + c["router"]) + c["head"])
    attn = ((s["dense"] + s["moe"]) * 2 * s["H"]
            * (s["nope"] + s["rope"] + s["vd"]) * context)
    return 2.0 * matmul + attn


def expert_flops(src: dict, picks: float) -> float:
    """FLOPs of ``picks`` tokens through one held expert each."""
    return 2.0 * param_counts(src)["expert"] * picks


def serve_flops(src: dict, first: int, n: int) -> float:
    """:func:`token_flops` of ``n`` tokens fed at positions ``first`` ...
    ``first + n - 1``, each over its context up to and including itself."""
    contexts = n * (first + 1) + n * (n - 1) / 2
    return n * token_flops(src, 0) + (token_flops(src, 1) -
                                      token_flops(src, 0)) * contexts


def experts_least(src: dict, peak: dict, touched: float, routed: float,
                  dtype: str = "bfloat16") -> float:
    """Least time of the routed experts' grouped matmuls: the ``touched``
    held experts' weights read once, ``routed`` picks' FLOPs."""
    w = param_counts(src)["expert"] * DTYPE_BYTES[dtype] * touched
    return max(w / peak["hbm_bytes_per_s"],
               expert_flops(src, routed) / peak["bf16_flops_per_s"])


def decode_tick_least(src: dict, peak: dict, contexts, touched: float,
                      routed: float, dtype: str = "bfloat16") -> float:
    """Least time of one decode tick over slots whose caches hold
    ``contexts`` tokens before it: every weight but the embedding and the
    routed experts read once, the ``touched`` held experts read once, the
    embedding rows of the new tokens, each slot's cache entries and the
    new ones; FLOPs of each new token over its context and of the
    ``routed`` held picks.  The larger of bytes at peak bandwidth and FLOPs
    at peak."""
    s = _sizes(src)
    c = param_counts(src)
    b = DTYPE_BYTES[dtype]
    n = len(contexts)
    fixed = c["total"] - c["embed"] - s["moe"] * s["held"] * c["expert"]
    bytes_ = ((fixed + touched * c["expert"] + n * s["D"]) * b
              + latent_bytes_per_token(src, dtype) * (sum(contexts) + n))
    flops = (sum(token_flops(src, ctx + 1) for ctx in contexts)
             + expert_flops(src, routed))
    return max(bytes_ / peak["hbm_bytes_per_s"],
               flops / peak["bf16_flops_per_s"])


COMMIT = "serve.engine.commit"


def expert_counters(run, step):
    """(``routed_local``, ``experts_touched``) that the engine's commit span
    inside the traced ``serve.step`` span ``step`` records, or None where it
    records none (a program without the counters)."""
    for s in run.trace.spans_named(COMMIT):
        if step.start <= s.start and s.end <= step.end \
                and "routed_local" in s.stats:
            return int(s.stats["routed_local"]), int(s.stats["experts_touched"])
    return None
