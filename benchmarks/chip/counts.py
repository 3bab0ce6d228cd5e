"""Operations and bytes the algorithm needs, computed from a configuration's
published sizes (its source keys), independent of any implementation.

Every roofline share and utilisation of the benchmark divides one of these
by a measured time, so they read the same work whatever computes it.
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(src: dict):
    D, H = src["hidden_size"], src["num_attention_heads"]
    dh = src.get("head_dim") or D // H
    return (src["num_hidden_layers"], D, H, src["num_key_value_heads"], dh,
            src["intermediate_size"], src["vocab_size"])


def param_counts(src: dict) -> dict:
    """Parameters of a dense GQA decoder, split as the FLOP counts need.

    ``matmul`` counts every weight a token multiplies through (the tied
    embedding once more, as the output head); ``total`` counts each stored
    parameter once.
    """
    L, D, H, Hkv, dh, F, V = _dims(src)
    attn = D * H * dh + 2 * D * Hkv * dh + H * dh * D
    mlp = 3 * D * F
    norms = 2 * D + (2 * dh if src.get("model_type") == "qwen3" else 0)
    layer = attn + mlp + norms
    embed = V * D
    head = 0 if src.get("tie_word_embeddings") else V * D
    total = L * layer + D + embed + head
    return {"layer": layer, "layers": L * layer, "embed": embed,
            "total": total,
            "nonembed_matmul": L * (attn + mlp), "unembed_matmul": V * D}


def active_params(src: dict) -> float:
    """Parameters touched per token (MoE: shared + top-k routed only); the
    arithmetic of ``benchmarks/roofline.py``, on the source keys."""
    total = param_counts(src)["total"]
    n_exp = src.get("num_experts") or src.get("n_routed_experts") or 0
    if not n_exp:
        return float(total)
    n_moe_layers = src["num_hidden_layers"] - src.get("first_k_dense_replace",
                                                      0)
    per_expert = 3 * src["hidden_size"] * src["moe_intermediate_size"]
    routed = n_moe_layers * n_exp * per_expert
    active_frac = src["num_experts_per_tok"] / n_exp
    return float(total - routed * (1.0 - active_frac))


def kv_bytes_per_token(src: dict, dtype: str = "bfloat16") -> int:
    L, _, _, Hkv, dh, _, _ = _dims(src)
    return L * 2 * Hkv * dh * DTYPE_BYTES[dtype]


def matmul_flops_per_token(src: dict) -> float:
    """2 x (non-embedding + output-head weights): one token's forward."""
    c = param_counts(src)
    return 2.0 * (c["nonembed_matmul"] + c["unembed_matmul"])


def attn_flops(src: dict, context: int) -> float:
    """Forward attention FLOPs of one query token over ``context`` keys:
    scores and the weighted sum, 2 FLOPs per multiply-add each."""
    L, _, H, _, dh, _, _ = _dims(src)
    return 4.0 * L * H * dh * context


def serve_token_flops(src: dict, context: int) -> float:
    """Forward FLOPs of one token (fed or generated) at ``context``."""
    return matmul_flops_per_token(src) + attn_flops(src, context)


def serve_flops(src: dict, first: int, n: int) -> float:
    """Forward FLOPs of ``n`` tokens fed at positions ``first`` ...
    ``first + n - 1``, each over its context up to and including itself."""
    contexts = n * (first + 1) + n * (n - 1) / 2
    return n * matmul_flops_per_token(src) + attn_flops(src, contexts)


def train_flops_per_token(src: dict, seq: int) -> float:
    """Forward + backward (3x forward) per token of a causal sequence of
    ``seq``; recomputation is not counted."""
    return 3.0 * (matmul_flops_per_token(src) + attn_flops(src, (seq + 1) / 2))


def decode_tick_least(src: dict, peak: dict, contexts, dtype="bfloat16"):
    """Least time of one decode tick over slots whose caches hold
    ``contexts`` tokens before the tick: every weight read once, each slot's
    live K/V read, the new token's K/V written, and the FLOPs of each new
    token over its context plus itself.  Returns (seconds, "memory" |
    "compute")."""
    w = param_counts(src)["total"] * DTYPE_BYTES[dtype]
    kv = kv_bytes_per_token(src, dtype)
    n = len(contexts)
    bytes_ = w + kv * sum(contexts) + kv * n
    flops = sum(serve_token_flops(src, c + 1) for c in contexts)
    t_mem = bytes_ / peak["hbm_bytes_per_s"]
    t_cmp = flops / peak["bf16_flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")
