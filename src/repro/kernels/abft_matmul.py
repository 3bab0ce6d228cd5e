"""ABFT row/column-checksummed error-injected int8 matmul (§V).

Algorithm-based fault tolerance over the over-scaled MXU: the kernel runs
the same error-injected systolic matmul as ``overscale_matmul`` (int8 x
int8 -> int32 accumulators, MSB/carry-weighted bit flips at the final K
block) and *fuses* the row/column checksums of the corrupted product into
the same pass — no second trip over C in HBM.  Detection compares them
against the protected references

    row_ref = A @ colsum(B)        col_ref = rowsum(A) @ B

computed from the (clean) inputs; int32 arithmetic wraps mod 2^32 on both
sides, so a flipped bit b shows up as a +-2^b syndrome regardless of
accumulator overflow.  A single flipped element (i, j) satisfies
``dr[i] == dc[j]`` and is repaired exactly; see
``repro.tolerance.abft.detect_and_correct``.

Block structure mirrors ``overscale_matmul`` (K-major grid, int32 VMEM
accumulator scratch, flips at k == n_k-1).  The checksums come out as
per-block partial sums — ``rs_part[(i, j)]`` holds the rowsum of C's
(i, j) block broadcast over one lane tile, ``cs_part`` the colsum over one
sublane tile — written exactly once per block (no non-contiguous output
revisits), and are reduced outside the kernel (a (M, n_j) / (n_i, N) sum,
negligible next to the matmul).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.overscale_matmul import BK, BM, BN, CDF_SPEC, inject_flips

_LANE = 128   # lane tile carrying the broadcast row checksums
_SUB = 8      # sublane tile carrying the broadcast column checksums


def _kernel(a_ref, b_ref, gate_ref, bit_ref, cdf_ref, c_ref, rs_ref, cs_ref,
            acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 on the MXU (exact: no int32 x int32 matmul)
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _finalize():
        c = inject_flips(acc_ref[...], gate_ref[...], bit_ref[...], cdf_ref)
        c_ref[...] = c
        # fused checksums OF THE CORRUPTED PRODUCT: the syndromes vs the
        # protected references localize exactly the injected flips
        rs_ref[...] = jnp.broadcast_to(
            jnp.sum(c, axis=1, keepdims=True), rs_ref.shape)
        cs_ref[...] = jnp.broadcast_to(
            jnp.sum(c, axis=0, keepdims=True), cs_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def abft_matmul(a, b, u_gate, u_bit, cdf, *,
                interpret: Optional[bool] = None):
    """a:(M,K) int8, b:(K,N) int8, u_gate/u_bit:(M,N) uint32, cdf:(33,)
    float32 -> (c:(M,N) int32 with injected errors, rowsum:(M,) int32,
    colsum:(N,) int32) — checksums of the corrupted product."""
    interpret = resolve_interpret(interpret)
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    Mp, Np, Kp = (-(-M // BM) * BM), (-(-N // BN) * BN), (-(-K // BK) * BK)
    a = jnp.pad(a, ((0, Mp - M), (0, Kp - K)))
    b = jnp.pad(b, ((0, Kp - K), (0, Np - N)))
    # pad the gate planes with u ~= 1.0 (never < p_total): a flip injected
    # into the zero padding would poison the fused checksums
    full = np.uint32(0xFFFFFFFF)
    u_gate = jnp.pad(u_gate, ((0, Mp - M), (0, Np - N)), constant_values=full)
    u_bit = jnp.pad(u_bit, ((0, Mp - M), (0, Np - N)), constant_values=full)
    n_k = Kp // BK
    n_i, n_j = Mp // BM, Np // BN
    grid = (n_i, n_j, n_k)
    c, rs_part, cs_part = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BK), lambda i, j, k: (i, k)),
            pl.BlockSpec((BK, BN), lambda i, j, k: (k, j)),
            pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
            pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
            CDF_SPEC,
        ],
        out_specs=[
            pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
            pl.BlockSpec((BM, _LANE), lambda i, j, k: (i, j)),
            pl.BlockSpec((_SUB, BN), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
            jax.ShapeDtypeStruct((Mp, n_j * _LANE), jnp.int32),
            jax.ShapeDtypeStruct((n_i * _SUB, Np), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.int32)],
        interpret=interpret,
    )(a, b, u_gate, u_bit, cdf)
    # reduce the per-block partials (int32 wraps commute with the split)
    rowsum = jnp.sum(rs_part.reshape(Mp, n_j, _LANE)[:, :, 0], axis=1)
    colsum = jnp.sum(cs_part.reshape(n_i, _SUB, Np)[:, 0, :], axis=0)
    return c[:M, :N], rowsum[:M], colsum[:N]


def checksum_refs(a, b):
    """Protected checksum references from the (clean) int8 inputs:
    ``row_ref = A @ colsum(B)``, ``col_ref = rowsum(A) @ B`` — int32,
    wrapping mod 2^32 exactly like the accumulators they guard."""
    a32 = a.astype(jnp.int32)
    b32 = b.astype(jnp.int32)
    return a32 @ jnp.sum(b32, axis=1), jnp.sum(a32, axis=0) @ b32
