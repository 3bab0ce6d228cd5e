"""Pallas TPU kernel: FlashAttention (blockwise online-softmax), causal.

Single-head formulation q:(S,D), k/v:(T,D); batch x heads handled by ``vmap``
over the ``pallas_call`` (maps onto leading grid dimensions). Grid is
(num_q_blocks, num_kv_blocks) with the kv axis iterating fastest; the running
max / denominator / accumulator live in VMEM scratch that persists across the
kv sweep for one q block (the canonical revisited-output-block pattern).

BlockSpec tiling: q/o blocks (BQ, D), k/v blocks (BK, D) — MXU-aligned for
D in {64, 128, 256}; the (BQ, BK) score tile stays in registers/VMEM and the
(S, T) score matrix is never materialized in HBM.
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


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, n_k: int, causal: bool, scale: float):
    iq = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + jax.lax.dot_general(
                        p, v_ref[...].astype(jnp.float32),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)[:, None]
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: Optional[bool] = None):
    """q:(S,D), k/v:(T,D) -> (S,D). vmap for batch/heads."""
    interpret = resolve_interpret(interpret)
    S, D = q.shape
    T = k.shape[0]
    bq = min(bq, S)
    bk = min(bk, T)
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    n_q, n_k = S // bq, T // bk
    scale = 1.0 / (D ** 0.5)
    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, n_k=n_k, causal=causal,
                          scale=scale),
        grid=(n_q, n_k),
        in_specs=[
            pl.BlockSpec((bq, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, D), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, D), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
