"""jit'd public wrappers around the Pallas kernels.

Every kernel picks its mode through :func:`repro.kernels.resolve_interpret`:
compiled on a TPU backend, the interpreter on the CPU backend (interpret
mode executes the kernel body for correctness validation).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.abft_matmul import abft_matmul as _abft, checksum_refs
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.mamba_scan import mamba_scan as _mamba
from repro.kernels.overscale_matmul import (bit_probs_to_cdf,
                                            make_int8_error_matmul,
                                            overscale_matmul as _omm,
                                            quantize)
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.thermal_stencil import thermal_stencil as _stencil


def flash_attention_bh(q, k, v, *, causal=True, bq=128, bk=128):
    """Batched/multi-head wrapper: q:(B,S,H,D), k/v:(B,T,H,D)."""
    def one(q1, k1, v1):
        return _flash(q1, k1, v1, causal=causal, bq=bq, bk=bk)

    return jax.vmap(jax.vmap(one, in_axes=(1, 1, 1), out_axes=1))(q, k, v)


def paged_attention_decode(q, k_pool, v_pool, ids_pool, block_table, pos, *,
                           window=0):
    """Paged single-token decode: q:(B,H,D), pools:(P,ps,Hkv,D)/(P,ps),
    block_table:(B,n_pages) physical page ids, pos:(B,) query positions."""
    return _paged(q, k_pool, v_pool, ids_pool, block_table, pos,
                  window=window)


def mamba_scan_b(xh, dt, A, B, C, *, chunk=256):
    """Batched wrapper: xh:(b,S,H,P), dt:(b,S,H), B/C:(b,S,H,N)."""
    def one(x1, d1, b1, c1):
        return _mamba(x1, d1, A, b1, c1, chunk=chunk)

    return jax.vmap(one)(xh, dt, B, C)


def thermal_sweep(T, P, diag, *, g_lat, g_v_tamb, iters=64, phase=None):
    return _stencil(T, P, diag, g_lat=g_lat, g_v_tamb=g_v_tamb, iters=iters,
                    phase=phase)


def overscale_mm(a, b, u_gate, u_bit, cdf):
    return _omm(a, b, u_gate, u_bit, cdf)


def abft_mm(a, b, u_gate, u_bit, cdf):
    """Error-injected int8 matmul with fused row/column checksums:
    -> (c, rowsum, colsum)."""
    return _abft(a, b, u_gate, u_bit, cdf)
