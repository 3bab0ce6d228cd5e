"""Pallas TPU kernel: paged-attention decode (block-table K/V gather).

One query token per slot attends a KV cache that lives as **non-contiguous
physical pages**: ``k/v`` pools are ``(P, page_size, Hkv, D)`` with one page
on the leading axis, and each slot's ``block_table`` row names the physical
pages that make up its logical sequence.  The kernel never materializes the
gathered logical cache — the grid is ``(slots, pages_per_slot)`` with the
page axis iterating fastest, and the **scalar-prefetched** block table
drives the K/V BlockSpec index maps so each page is DMA'd into VMEM
directly from its arbitrary pool position (the vLLM PagedAttention access
pattern).  Running max / denominator / accumulator live in VMEM scratch
across one slot's page sweep (the same revisited-output-block pattern as
``flash_attention``).

Masking is the serving tier's ragged contract, evaluated per entry from the
page's ``pos_ids``: ``valid = (0 <= id <= pos) [and id > pos - window]`` —
so dense caches, sliding-window rings (arbitrary id layout within a page),
the permanently invalid null page (``id = -1``), and rows disabled with
``pos = -1`` (``n_valid = 0``) all fall out of one rule.

GQA is handled in-kernel: q ``(H, D)`` is reshaped to ``(Hkv, G, D)`` and
scored against the page's ``(page_size, Hkv, D)`` K with a batched
dot_general — no vmap over heads, one pallas_call per batch.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, ids_ref, o_ref,
            m_ref, l_ref, acc_ref, *, n_pages: int, hkv: int, g: int,
            scale: float, window: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d = q_ref.shape[-1]
    q = q_ref[0].astype(jnp.float32).reshape(hkv, g, d)
    k = k_ref[0].astype(jnp.float32).transpose(1, 0, 2)  # (hkv, ps, d)
    v = v_ref[0].astype(jnp.float32).transpose(1, 0, 2)
    # scores (hkv, g, ps): batched over kv heads, contracted over d
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    ids = ids_ref[0]  # (ps,) absolute positions; -1 = invalid / null page
    p_i = pos_ref[i]
    valid = (ids >= 0) & (ids <= p_i)
    if window:
        valid &= ids > p_i - window
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    # multiply (not just NEG_INF-mask) so a fully-masked row — the null
    # page, or pos = -1 — keeps l at exactly 0 (exp(NEG_INF - NEG_INF) is
    # 1, not 0) and finalizes to a zero output instead of mean(v)
    p = jnp.exp(s - m_new[..., None]) * valid[None, None, :]
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = (acc_ref[...] * corr[..., None]
                    + jax.lax.dot_general(
                        p, v, (((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finalize():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = o.reshape(hkv * g, d).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention(q, k_pool, v_pool, ids_pool, block_table, pos, *,
                    window: int = 0, interpret: Optional[bool] = None):
    """Paged single-token decode attention.

    q:(B,H,D), k/v pool:(P,ps,Hkv,D), ids pool:(P,ps) int32,
    block_table:(B,n_pages) int32 physical page ids, pos:(B,) int32 query
    positions (-1 disables a row -> zero output).  ``window`` > 0 adds the
    sliding-window bound.  Returns (B,H,D).
    """
    interpret = resolve_interpret(interpret)
    B, H, D = q.shape
    P, ps, Hkv, _ = k_pool.shape
    n_pages = block_table.shape[1]
    G = H // Hkv
    scale = 1.0 / (D ** 0.5)
    bt = jnp.asarray(block_table, jnp.int32).reshape(-1)  # (B * n_pages,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda i, j, bt, pos: (i, 0, 0)),
            pl.BlockSpec((1, ps, Hkv, D),
                         lambda i, j, bt, pos: (bt[i * n_pages + j], 0, 0, 0)),
            pl.BlockSpec((1, ps, Hkv, D),
                         lambda i, j, bt, pos: (bt[i * n_pages + j], 0, 0, 0)),
            pl.BlockSpec((1, ps),
                         lambda i, j, bt, pos: (bt[i * n_pages + j], 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda i, j, bt, pos: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_pages=n_pages, hkv=Hkv, g=G,
                          scale=scale, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
    )(bt, jnp.asarray(pos, jnp.int32), q, k_pool, v_pool, ids_pool)
