"""Attention: GQA (qk-norm, sliding-window), MLA (+absorbed decode), cross-attn.

KV caches are dicts of arrays with an explicit per-slot ``pos_ids`` table
(``(B, T)``) so full and ring-buffer (sliding-window) caches share one
masking rule, evaluated per batch row:
    valid(b, t) = 0 <= pos_ids[b, t] <= pos[b]  and  pos_ids[b, t] > pos[b] - window.

Decode is *ragged*: ``pos`` may be a scalar (the legacy slot-synchronous
engine) or a ``(B,)`` vector of per-slot positions, and the new-token axis
``S`` may exceed 1 (a chunked-prefill "extend" — each row appends up to S
tokens at its own offset; ``n_valid`` marks how many are real, padded tails
write ``pos_id = -1`` and stay invisible to the mask).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamMeta, dense
from repro.models.layers import apply_rope, rms_norm, yarn_attn_mscale
from repro.sharding.plan import Plan

NEG_INF = -1e30


def decode_positions(pos, B: int, S: int):
    """Absolute query positions ``(B, S)`` from a scalar or ``(B,)`` pos."""
    p = jnp.asarray(pos, jnp.int32)
    if p.ndim == 0:
        p = jnp.broadcast_to(p, (B,))
    return p[:, None] + jnp.arange(S, dtype=jnp.int32)[None]


def _row_update(arr, new, start):
    """Write ``new`` (B,S,...) into ``arr`` (B,T,...) at per-row offsets."""
    return jax.vmap(
        lambda a, n, s: jax.lax.dynamic_update_slice_in_dim(a, n, s, axis=0)
    )(arr, new.astype(arr.dtype), start)


def _ring_scatter(arr, new, start, n_valid):
    """Write ``new`` (B,S,...) into ring ``arr`` (B,T,...) at per-row offsets
    modulo T.  Unlike ``_row_update`` (whose dynamic_update_slice *clamps*
    ``start`` so a chunk touching the ring edge lands shifted), entries wrap
    index-wise, and rows' padded tails (past ``n_valid``) are masked out so
    they never overwrite live window entries.  Requires ``S <= T`` (one
    chunk may not lap the window; scatter indices must stay unique)."""
    T, S = arr.shape[1], new.shape[1]
    if S > T:
        raise ValueError(f"chunk of {S} tokens would lap the {T}-entry ring")
    offs = jnp.arange(S, dtype=jnp.int32)
    keep = (jnp.ones((new.shape[0], S), bool) if n_valid is None
            else offs[None] < jnp.asarray(n_valid, jnp.int32)[:, None])

    def row(a, n, s, kb):
        idx = (s + offs) % T
        upd = jnp.where(kb.reshape((S,) + (1,) * (a.ndim - 1)),
                        n.astype(a.dtype), a[idx])
        return a.at[idx].set(upd)

    return jax.vmap(row)(arr, new, start, keep)


def _new_pos_ids(positions, n_valid):
    """Position ids to record for an appended chunk: the absolute position,
    or -1 (invalid) past each row's ``n_valid`` real tokens."""
    if n_valid is None:
        return positions
    S = positions.shape[1]
    keep = jnp.arange(S, dtype=jnp.int32)[None] < \
        jnp.asarray(n_valid, jnp.int32)[:, None]
    return jnp.where(keep, positions, -1)


# =============================================================================
# GQA
# =============================================================================

def gqa_params(cfg: ModelConfig, plan: Plan, cross: bool = False):
    d, dh = cfg.d_model, cfg.head_dim
    h, hkv = plan.num_heads, plan.num_kv_heads
    p = {
        "wq": ParamMeta((d, h, dh), ("embed", "heads", None), fan_in=d),
        "wk": ParamMeta((d, hkv, dh), ("embed", "kv_heads", None), fan_in=d),
        "wv": ParamMeta((d, hkv, dh), ("embed", "kv_heads", None), fan_in=d),
        "wo": ParamMeta((h, dh, d), ("heads", None, "embed"), fan_in=h * dh),
    }
    if cfg.qk_norm:
        p["q_norm"] = ParamMeta((dh,), (None,), init="ones")
        p["k_norm"] = ParamMeta((dh,), (None,), init="ones")
    if cross:
        p["gate"] = ParamMeta((1,), (None,), init="zeros")
    return p


def _qkv(p, x, kv_x, cfg: ModelConfig, plan: Plan):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", kv_x, p["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", kv_x, p["wv"].astype(dt))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


BLOCKWISE_THRESHOLD = 8192  # self-attention seqs >= this use blockwise softmax
# (§Perf iteration 6 tried 4096: REFUTED — at 4k the 2x2 block grid computes
# the same flops and the scan stacking overhead exceeds the score-matrix
# saving; blockwise pays off from 8k where scores no longer fit)


def _sdpa(q, k, v, mask, plan: Plan, scale=None):
    """q:(B,S,H,D) k,v:(B,T,Hkv,D) mask:(B,1,1,S,T) or None -> (B,S,H,D);
    scores are scaled by ``scale``, by default ``D ** -0.5``."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    q = q.reshape(B, S, Hkv, G, D)
    # accumulate in f32 via the dot itself — casting inputs would materialize
    # f32 copies of K (and force an f32 cache carry through the decode scan)
    scores = jnp.einsum("bshgd,bthd->bhgst", q, k,
                        preferred_element_type=jnp.float32)
    if scale is None:
        scores = scores / jnp.sqrt(D).astype(jnp.float32)
    else:
        scores = scores * scale
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhgst,bthd->bshgd", w, v)
    return o.reshape(B, S, H, v.shape[-1])  # v head dim may differ (MLA)


def blockwise_sdpa(q, k, v, *, causal: bool, window: int = 0,
                   q_block: int = 2048, kv_block: int = 2048, scale=None):
    """Flash-style online-softmax attention in pure XLA (scan over blocks).

    Never materializes the (S,T) score matrix — per-step live memory is
    O(q_block × kv_block). Used for long self-attention (32k prefill) where
    the naive path would need S² score buffers. q:(B,S,H,D), k/v:(B,T,Hkv,D).
    """
    B, S, H, D = q.shape
    T = k.shape[1]
    Hkv = k.shape[2]
    G = H // Hkv
    Dv = v.shape[-1]
    q_block = min(q_block, S)
    kv_block = min(kv_block, T)
    nq, nk = S // q_block, T // kv_block
    if scale is None:
        scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)

    qb = q.reshape(B, nq, q_block, Hkv, G, D)
    kb = k.reshape(B, nk, kv_block, Hkv, D)
    vb = v.reshape(B, nk, kv_block, Hkv, Dv)

    def q_step(_, qi_inp):
        qi, iq = qi_inp  # (B,q_block,Hkv,G,D), scalar block index

        def kv_step(carry, kv_inp):
            m, l, acc = carry
            kj, vj, jk = kv_inp
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kj,
                           preferred_element_type=jnp.float32)
            s = s * scale
            qpos = iq * q_block + jnp.arange(q_block)[:, None]
            kpos = jk * kv_block + jnp.arange(kv_block)[None, :]
            valid = jnp.ones((q_block, kv_block), bool)
            if causal:
                valid &= kpos <= qpos
            if window:
                valid &= kpos > qpos - window
            s = jnp.where(valid[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vj.dtype), vj).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_block, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kb.swapaxes(0, 1), vb.swapaxes(0, 1), jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(v.dtype)  # (B,Hkv,G,q_block,Dv)

    _, outs = jax.lax.scan(
        q_step, None, (qb.swapaxes(0, 1), jnp.arange(nq)))
    # outs: (nq, B, Hkv, G, q_block, Dv)
    out = jnp.moveaxis(outs, 0, 3)  # (B,Hkv,G,nq,q_block,Dv)
    out = out.reshape(B, Hkv, G, S, Dv).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, S, H, Dv)


def causal_mask(S: int, T: int, q_offset, window: int = 0):
    """(1,1,1,S,T) bool; q position i attends kv position j iff j<=i (+window)."""
    qi = q_offset + jnp.arange(S)[:, None]
    kj = jnp.arange(T)[None, :]
    m = kj <= qi
    if window:
        m &= kj > qi - window
    return m[None, None, None]


def gqa_apply(p, x, cfg: ModelConfig, plan: Plan, positions=None,
              kv_x=None, cross: bool = False, causal: bool = True):
    """Train/prefill path. x:(B,S,D). Returns (out, kv) — kv for cache seeding."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, x, x if kv_x is None else kv_x, cfg, plan)
    if positions is None:
        positions = jnp.arange(S)[None]
    if not cross:
        q = apply_rope(q, positions, cfg)
        k = apply_rope(k, positions, cfg)
        mask = (causal_mask(S, k.shape[1], 0, cfg.sliding_window)
                if causal else None)
    else:
        mask = None
    q = plan.act(q, "batch", None, "heads", None)
    k = plan.act(k, "batch", None, "kv_heads", None)
    if not cross and causal and S == k.shape[1] and S >= BLOCKWISE_THRESHOLD:
        o = blockwise_sdpa(q, k, v, causal=True, window=cfg.sliding_window)
    else:
        o = _sdpa(q, k, v, mask, plan)
    o = jnp.einsum("bshd,hdk->bsk", o, p["wo"].astype(x.dtype))
    if cross:
        o = o * jnp.tanh(p["gate"].astype(x.dtype))
    return o, (k, v)


# --- decode ------------------------------------------------------------------

def gqa_cache_init(cfg: ModelConfig, plan: Plan, batch: int, max_len: int, dtype):
    T = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    hkv, dh = plan.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, T, hkv, dh), dtype),
        "v": jnp.zeros((batch, T, hkv, dh), dtype),
        "pos_ids": jnp.full((batch, T), -1, jnp.int32),
    }


def gqa_cache_abstract(cfg: ModelConfig, plan: Plan, batch: int, max_len: int, dtype):
    T = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    hkv, dh = plan.num_kv_heads, cfg.head_dim
    return {
        "k": jax.ShapeDtypeStruct((batch, T, hkv, dh), dtype),
        "v": jax.ShapeDtypeStruct((batch, T, hkv, dh), dtype),
        "pos_ids": jax.ShapeDtypeStruct((batch, T), jnp.int32),
    }


def gqa_cache_spec(plan: Plan, seq_axis=None):
    b = plan.batch_axes
    kvh = plan.rules.get("kv_heads")
    from jax.sharding import PartitionSpec as P
    return {"k": P(b, seq_axis, kvh, None), "v": P(b, seq_axis, kvh, None),
            "pos_ids": P(b, seq_axis)}


def gqa_decode(p, x, cache, pos, cfg: ModelConfig, plan: Plan, n_valid=None):
    """Ragged decode/extend. x:(B,S,D); pos: scalar or (B,) per-slot position.

    Appends S new tokens per row at that row's own offset (ring-modded for
    sliding-window caches). ``n_valid`` (B,) optionally marks how many of the
    S tokens are real per row; padded tails record ``pos_id = -1``.
    """
    B, S, _ = x.shape
    q, k_new, v_new = _qkv(p, x, x, cfg, plan)
    positions = decode_positions(pos, B, S)  # (B,S)
    q = apply_rope(q, positions, cfg)
    k_new = apply_rope(k_new, positions, cfg)
    T = cache["k"].shape[1]
    start = positions[:, 0] % T  # ring for SWA; == pos when T == max_len
    ids = _new_pos_ids(positions, n_valid)
    if cfg.sliding_window:
        # ring cache: token j of the chunk evicts the entry at
        # (pos+j) % T, which for S > 1 may still be inside token i < j's
        # window — so attend against the PRE-update ring plus the chunk's
        # own K/V, then scatter (wrapped, padded tails masked off).
        def win_mask(entry_pos):  # (B,T') -> (B,S,T') validity
            e = entry_pos[:, None, :]
            return ((e >= 0) & (e <= positions[..., None])
                    & (e > positions[..., None] - cfg.sliding_window))
        mask = jnp.concatenate(
            [win_mask(cache["pos_ids"]), win_mask(ids)],
            axis=-1)[:, None, None]  # (B,1,1,S,T+S)
        o = _sdpa(q, jnp.concatenate([cache["k"], k_new], axis=1),
                  jnp.concatenate([cache["v"], v_new], axis=1), mask, plan)
        k = _ring_scatter(cache["k"], k_new, start, n_valid)
        v = _ring_scatter(cache["v"], v_new, start, n_valid)
        pos_ids = _ring_scatter(cache["pos_ids"], ids, start, n_valid)
    else:
        k = _row_update(cache["k"], k_new, start)
        v = _row_update(cache["v"], v_new, start)
        pos_ids = _row_update(cache["pos_ids"], ids, start)  # (B,T)
        o = _attend_full(q, k, v, pos_ids, positions, plan)
    o = jnp.einsum("bshd,hdk->bsk", o, p["wo"].astype(x.dtype))
    return o, {"k": k, "v": v, "pos_ids": pos_ids}


def _attend_full(q, k, v, pos_ids, positions, plan: Plan):
    """Attention over a full-length cache (B,T,...) under the position-table
    mask: query (b, s) sees entry t iff
    ``0 <= pos_ids[b, t] <= positions[b, s]``."""
    valid = (pos_ids >= 0)[:, None, :] & \
        (pos_ids[:, None, :] <= positions[..., None])
    return _sdpa(q, k, v, valid[:, None, None], plan)  # mask (B,1,1,S,T)


def gqa_decode_paged(p, x, pool, layer, bt, pos, cfg: ModelConfig,
                     plan: Plan, n_valid=None):
    """Ragged decode/extend against a page pool, for full-length GQA caches.

    ``pool`` holds every layer's pages: ``k``/``v`` (L, P, page, Hkv, D) and
    ``pos_ids`` (L, P, page); ``bt`` (B, W) maps each row's logical page j
    to a physical page.  Layer ``layer`` writes the chunk's new entries in
    place (position p of row b lands on page ``bt[b, p // page]`` at offset
    ``p % page``), then reads its pages through ``bt`` as a (B, W*page)
    logical view and attends exactly as :func:`gqa_decode` does.  Entries
    past ``n_valid`` and positions past ``W * page`` are never written.
    """
    B, S, _ = x.shape
    q, k_new, v_new = _qkv(p, x, x, cfg, plan)
    positions = decode_positions(pos, B, S)  # (B,S)
    q = apply_rope(q, positions, cfg)
    k_new = apply_rope(k_new, positions, cfg)
    pool = _paged_write(pool, layer, bt, positions,
                        _new_pos_ids(positions, n_valid),
                        {"k": k_new, "v": v_new})
    view = _paged_read(pool, layer, bt)
    o = _attend_full(q, view["k"], view["v"], view["pos_ids"], positions,
                     plan)
    o = jnp.einsum("bshd,hdk->bsk", o, p["wo"].astype(x.dtype))
    return o, pool


def _paged_write(pool, layer, bt, positions, ids, new):
    """Write a chunk's new entries ``new`` ({leaf: (B,S,...)}) and their
    position ids ``ids`` (B,S) into the page pool: position p of row b lands
    on page ``bt[b, p // page]`` at offset ``p % page``.  Entries with
    ``ids < 0`` (past ``n_valid``) and positions past the block table are
    dropped, never written.  ``layer`` indexes the pool's leading layer
    axis, or is None for a pool leaf of one layer."""
    lead = () if layer is None else (layer,)
    n_pages, page = pool["pos_ids"].shape[len(lead):]
    W = bt.shape[1]
    with jax.named_scope("kv_write"):
        j = positions // page
        keep = (ids >= 0) & (j < W)
        dest = jnp.where(
            keep, jnp.take_along_axis(bt, jnp.minimum(j, W - 1), axis=1),
            n_pages)  # out of range: the scatter drops it
        at = lead + (dest, positions % page)
        out = {name: pool[name].at[at].set(v.astype(pool[name].dtype),
                                           mode="drop")
               for name, v in new.items()}
        out["pos_ids"] = pool["pos_ids"].at[at].set(ids, mode="drop")
    return out


def _paged_read(pool, layer, bt):
    """Each row's pages of one layer through the block table, as a
    (B, W*page, ...) logical view of every leaf."""
    lead = () if layer is None else (layer,)
    B, W = bt.shape
    page = pool["pos_ids"].shape[-1]
    with jax.named_scope("kv_read"):
        return {name: leaf[lead + (bt,)].reshape(
                    (B, W * page) + leaf.shape[len(lead) + 2:])
                for name, leaf in pool.items()}


def gqa_seed_cache(cache, kv, prefill_len: int, lengths=None):
    """Write prefill-time K/V into a decode cache (assumes full, non-ring).

    ``lengths`` (B,) optionally marks per-row true prompt lengths for
    right-padded batched prefill: positions past a row's length record
    ``pos_id = -1`` so they stay invisible to the decode mask.
    """
    k, v = kv
    B = k.shape[0]
    T = cache["k"].shape[1]
    S = k.shape[1]
    if S > T:  # sliding-window cache shorter than prefill: keep the tail
        k, v = k[:, S - T:], v[:, S - T:]
        pos = jnp.arange(S - T, S, dtype=jnp.int32)
        S = T
    else:
        pos = jnp.arange(S, dtype=jnp.int32)
    pos2 = jnp.broadcast_to(pos[None], (B, S))
    if lengths is not None:
        pos2 = jnp.where(pos2 < jnp.asarray(lengths, jnp.int32)[:, None],
                         pos2, -1)
    out = {
        "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, 1),
        "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, 1),
        "pos_ids": jax.lax.dynamic_update_slice(
            cache["pos_ids"], pos2, (0, 0)),
    }
    return out


# =============================================================================
# MLA (deepseek-v2): low-rank compressed KV, absorbed decode, paged latent
# =============================================================================

def mla_params(cfg: ModelConfig, plan: Plan):
    d = cfg.d_model
    h = plan.num_heads
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qk = nope + rope_d
    p = {
        "kv_down": dense(d, cfg.kv_lora_rank + rope_d, "embed", None),
        "kv_norm": ParamMeta((cfg.kv_lora_rank,), (None,), init="ones"),
        "k_up": ParamMeta((cfg.kv_lora_rank, h, nope), (None, "heads", None),
                          fan_in=cfg.kv_lora_rank),
        "v_up": ParamMeta((cfg.kv_lora_rank, h, vd), (None, "heads", None),
                          fan_in=cfg.kv_lora_rank),
        "wo": ParamMeta((h, vd, d), ("heads", None, "embed"), fan_in=h * vd),
    }
    if cfg.q_lora_rank:
        p["q_down"] = dense(d, cfg.q_lora_rank, "embed", None)
        p["q_norm"] = ParamMeta((cfg.q_lora_rank,), (None,), init="ones")
        p["q_up"] = ParamMeta((cfg.q_lora_rank, h, qk), (None, "heads", None),
                              fan_in=cfg.q_lora_rank)
    else:
        p["q_up"] = ParamMeta((d, h, qk), ("embed", "heads", None), fan_in=d)
    return p


def _mla_q(p, x, cfg, positions):
    dt = x.dtype
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        cq = rms_norm(x @ p["q_down"].astype(dt), p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["q_up"].astype(dt))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["q_up"].astype(dt))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg, dim=rope_d)
    return q_nope, q_rope


def _mla_latent(p, x, cfg, positions):
    """Each token's cache entry: its normalised compressed KV (``kv_lora_rank``)
    followed by its roped shared key (``qk_rope_head_dim``), (B,T,r+rope)."""
    dt = x.dtype
    rope_d = cfg.qk_rope_head_dim
    kvd = x @ p["kv_down"].astype(dt)
    c_kv = rms_norm(kvd[..., : cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = kvd[..., cfg.kv_lora_rank:][:, :, None, :]  # (B,T,1,rope)
    k_rope = apply_rope(k_rope, positions, cfg, dim=rope_d)[:, :, 0]
    return jnp.concatenate([c_kv, k_rope.astype(c_kv.dtype)], -1)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``q_head_dim ** -0.5``, times YaRN's ``m ** 2`` where rope is YaRN."""
    return ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
            * yarn_attn_mscale(cfg) ** 2)


def _mla_expand(p, latent, cfg, dt):
    """Per-head keys and values from cache entries (…,T,r+rope):
    (…,T,H,nope+rope) and (…,T,H,v)."""
    r = cfg.kv_lora_rank
    c_kv, k_rope = latent[..., :r], latent[..., r:]
    k_nope = jnp.einsum("...tr,rhk->...thk", c_kv, p["k_up"].astype(dt))
    v = jnp.einsum("...tr,rhk->...thk", c_kv, p["v_up"].astype(dt))
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[..., None, :], k_nope.shape[:-1] + (k_rope.shape[-1],))], -1)
    return k, v


def mla_apply(p, x, cfg: ModelConfig, plan: Plan, positions=None):
    """Train/prefill: expand compressed KV per head; returns (out, latent)
    with the cache entries (B,S,r+rope) for cache seeding."""
    B, S, _ = x.shape
    dt = x.dtype
    if positions is None:
        positions = jnp.arange(S)[None]
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    latent = _mla_latent(p, x, cfg, positions)
    k, v = _mla_expand(p, latent, cfg, dt)
    q = jnp.concatenate([q_nope, q_rope], -1)
    q = plan.act(q, "batch", None, "heads", None)
    k = plan.act(k, "batch", None, "heads", None)
    scale = mla_softmax_scale(cfg)
    if S >= BLOCKWISE_THRESHOLD:
        o = blockwise_sdpa(q, k, v, causal=True, scale=scale)
    else:
        o = _sdpa(q, k, v, causal_mask(S, S, 0), plan, scale=scale)
    o = jnp.einsum("bshd,hdk->bsk", o, p["wo"].astype(dt))
    return o, latent


def mla_cache_init(cfg, plan, batch, max_len, dtype, abstract=False):
    """One leaf of cache entries, ``kv_lora_rank + qk_rope_head_dim`` values
    per token (576 for DeepSeek-V2), and the position table."""
    mk = jax.ShapeDtypeStruct if abstract else (lambda s, d: jnp.zeros(s, d))
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return {
        "latent": mk((batch, max_len, width), dtype),
        "pos_ids": (jax.ShapeDtypeStruct((batch, max_len), jnp.int32) if abstract
                    else jnp.full((batch, max_len), -1, jnp.int32)),
    }


def mla_cache_spec(plan: Plan, seq_axis=None):
    from jax.sharding import PartitionSpec as P
    b = plan.batch_axes
    return {"latent": P(b, seq_axis, None), "pos_ids": P(b, seq_axis)}


def _mla_attend(p, q_nope, q_rope, latent, pos_ids, positions,
                cfg: ModelConfig):
    """Attention of queries (B,S,H,·) over cache entries (B,T,r+rope) under
    the position-table mask, before the output projection: (B,S,H,v).

    A decode step (S = 1) is absorbed: ``k_up`` is folded into the query,
    which then scores the entries themselves, and ``v_up`` is applied to
    the weighted sum of entries.  A wider chunk (prefill) attends expanded:
    each row's entries are decompressed per head, one row at a time so the
    per-head keys, values and scores of only one row are live."""
    dt = q_nope.dtype
    r = cfg.kv_lora_rank
    scale = mla_softmax_scale(cfg)
    valid = (pos_ids >= 0)[:, None, :] & \
        (pos_ids[:, None, :] <= positions[..., None])  # (B,S,T)
    if q_nope.shape[1] == 1:
        q_c = jnp.einsum("bshk,rhk->bshr", q_nope, p["k_up"].astype(dt))
        q_cat = jnp.concatenate([q_c, q_rope], -1)  # (B,S,H,r+rope)
        scores = jnp.einsum("bshr,btr->bhst", q_cat, latent,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(valid[:, None], scores, NEG_INF)  # (B,H,S,T)
        w = jax.nn.softmax(scores, -1).astype(dt)
        ctx = jnp.einsum("bhst,btr->bshr", w, latent[..., :r])  # (B,S,H,r)
        return jnp.einsum("bshr,rhk->bshk", ctx, p["v_up"].astype(dt))

    def row(args):
        qn, qr, lat, ok = args  # (S,H,nope), (S,H,rope), (T,r+rope), (S,T)
        k, v = _mla_expand(p, lat, cfg, dt)  # (T,H,nope+rope), (T,H,v)
        q = jnp.concatenate([qn, qr], -1)
        scores = jnp.einsum("shd,thd->hst", q, k,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(ok[None], scores, NEG_INF)
        w = jax.nn.softmax(scores, -1).astype(dt)
        return jnp.einsum("hst,thd->shd", w, v)

    return jax.lax.map(row, (q_nope, q_rope, latent, valid))


def mla_decode(p, x, cache, pos, cfg: ModelConfig, plan: Plan, n_valid=None):
    """Ragged decode/extend against a slot-contiguous cache: ``pos`` scalar
    or (B,), S >= 1, per-row append at each row's own offset (full-length
    cache, no ring); see :func:`_mla_attend` for the two attention forms.
    """
    B, S, _ = x.shape
    positions = decode_positions(pos, B, S)  # (B,S)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)  # (B,S,H,nope/rope)
    new = _mla_latent(p, x, cfg, positions)  # (B,S,r+rope)
    start = positions[:, 0]
    latent = _row_update(cache["latent"], new, start)
    pos_ids = _row_update(cache["pos_ids"], _new_pos_ids(positions, n_valid),
                          start)  # (B,T)
    o = _mla_attend(p, q_nope, q_rope, latent, pos_ids, positions, cfg)
    o = jnp.einsum("bshd,hdk->bsk", o, p["wo"].astype(x.dtype))
    return o, {"latent": latent, "pos_ids": pos_ids}


def mla_decode_paged(p, x, pool, layer, bt, pos, cfg: ModelConfig,
                     plan: Plan, n_valid=None):
    """:func:`mla_decode` against a page pool of cache entries: ``latent``
    (L, P, page, r+rope) and ``pos_ids`` (L, P, page), or one layer's
    (P, page, ...) with ``layer`` None.  The chunk's valid entries are
    written into their pages (``kv_write``), then the layer's pages are
    read through the block table ``bt`` (``kv_read``) and attended as
    :func:`mla_decode` does."""
    B, S, _ = x.shape
    positions = decode_positions(pos, B, S)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    pool = _paged_write(pool, layer, bt, positions,
                        _new_pos_ids(positions, n_valid),
                        {"latent": _mla_latent(p, x, cfg, positions)})
    view = _paged_read(pool, layer, bt)
    o = _mla_attend(p, q_nope, q_rope, view["latent"], view["pos_ids"],
                    positions, cfg)
    o = jnp.einsum("bshd,hdk->bsk", o, p["wo"].astype(x.dtype))
    return o, pool


def mla_seed_cache(cache, latent, prefill_len: int, lengths=None):
    B, S = latent.shape[0], latent.shape[1]
    pos = jnp.arange(S, dtype=jnp.int32)
    pos2 = jnp.broadcast_to(pos[None], (B, S))
    if lengths is not None:
        pos2 = jnp.where(pos2 < jnp.asarray(lengths, jnp.int32)[:, None],
                         pos2, -1)
    return {
        "latent": jax.lax.dynamic_update_slice_in_dim(cache["latent"], latent,
                                                      0, 1),
        "pos_ids": jax.lax.dynamic_update_slice(cache["pos_ids"], pos2, (0, 0)),
    }
