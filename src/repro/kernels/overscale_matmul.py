"""Error-injected int8 matmul — the voltage over-scaling timing simulator.

TPU adaptation of the paper's post-P&R timing simulation (§III-D): instead of
gate-level simulating an FPGA netlist, we inject the *consequence* of timing
violations — bit flips in the 32-bit MAC accumulators, MSB/carry-weighted —
directly into the systolic matmul. The per-bit flip profile comes from
core/overscaling.error_profile.

Kernel: C[i,j] = sum_k A[i,k] * B[k,j] (int8 x int8 -> int32), then per
output element: with prob p_total flip one bit drawn from the bit-probability
distribution. Randomness enters as two uint32 planes (u_gate, u_bit) generated
outside (keeps the kernel deterministic and oracle-checkable).

BlockSpec tiling: (BM x BK) x (BK x BN) MXU-aligned blocks, K-major grid with
an int32 VMEM accumulator scratch (revisited output block pattern).  The
(33,) cdf sits whole in SMEM and is read as scalars: the bit lookup is a
static loop over the 32 thresholds, which the TPU lowering accepts where a
vector slice of a 1-D block is refused.
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

BM, BN, BK = 128, 128, 128
CDF_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole (33,) cdf


def _u32_to_f32(x):
    """uint32 -> float32 rounded once to nearest, as ``astype`` rounds; the
    TPU kernel lowering has no unsigned convert.  Both 16-bit halves are
    exact in float32, so ``hi * 2^16 + lo`` rounds only in the sum."""
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    hi = jax.lax.shift_right_logical(i, 16).astype(jnp.float32)
    lo = (i & 0xFFFF).astype(jnp.float32)
    return hi * 65536.0 + lo


def inject_flips(acc, gate, ubit, cdf_ref):
    """Flip one bit of each gated accumulator: ``acc`` (int32), ``gate`` /
    ``ubit`` (uint32 uniforms), ``cdf_ref`` the (33,) SMEM cdf
    ``[0, cdf..., p_total]``.  Same arithmetic as
    ``ref.overscale_matmul_ref``, so the result is bitwise equal to it."""
    p_total = cdf_ref[32]
    # flip gate: u < p_total (u uniform in [0,1))
    u = _u32_to_f32(gate) * (1.0 / 4294967296.0)
    flip = u < p_total
    # bit index: inverse-cdf lookup of second uniform scaled to p_total —
    # the number of cumulative per-bit probabilities cdf[1:33] <= u2
    u2 = _u32_to_f32(ubit) * (1.0 / 4294967296.0) * p_total
    bit_idx = jnp.zeros(acc.shape, jnp.int32)
    for t in range(1, 33):
        bit_idx += (u2 >= cdf_ref[t]).astype(jnp.int32)
    bit_idx = jnp.clip(bit_idx, 0, 31)
    mask = jnp.where(flip, jnp.left_shift(jnp.int32(1), bit_idx), 0)
    return jax.lax.bitwise_xor(acc, mask)


def _kernel(a_ref, b_ref, gate_ref, bit_ref, cdf_ref, c_ref, acc_ref, *,
            n_k: int):
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
        c_ref[...] = inject_flips(acc_ref[...], gate_ref[...], bit_ref[...],
                                  cdf_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def overscale_matmul(a, b, u_gate, u_bit, cdf, *,
                     interpret: Optional[bool] = None):
    """a:(M,K) int8, b:(K,N) int8, u_gate/u_bit:(M,N) uint32,
    cdf:(33,) float32 -> (M,N) int32 with injected errors."""
    interpret = resolve_interpret(interpret)
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    Mp, Np, Kp = (-(-M // BM) * BM), (-(-N // BN) * BN), (-(-K // BK) * BK)
    a = jnp.pad(a, ((0, Mp - M), (0, Kp - K)))
    b = jnp.pad(b, ((0, Kp - K), (0, Np - N)))
    u_gate = jnp.pad(u_gate, ((0, Mp - M), (0, Np - N)))
    u_bit = jnp.pad(u_bit, ((0, Mp - M), (0, Np - N)))
    n_k = Kp // BK
    grid = (Mp // BM, Np // BN, n_k)
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BK), lambda i, j, k: (i, k)),
            pl.BlockSpec((BK, BN), lambda i, j, k: (k, j)),
            pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
            pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
            CDF_SPEC,
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.int32)],
        interpret=interpret,
    )(a, b, u_gate, u_bit, cdf)
    return out[:M, :N]


def bit_probs_to_cdf(bit_probs) -> jnp.ndarray:
    p = jnp.asarray(bit_probs, jnp.float32)
    cdf = jnp.concatenate([jnp.zeros((1,), jnp.float32), jnp.cumsum(p)])
    return cdf  # (33,); cdf[-1] = p_total


# --- quantization helpers + app-facing wrapper --------------------------------

def quantize(x, bits: int = 8):
    scale = jnp.max(jnp.abs(x)) / (2 ** (bits - 1) - 1) + 1e-9
    q = jnp.clip(jnp.round(x / scale), -(2 ** (bits - 1)), 2 ** (bits - 1) - 1)
    return q.astype(jnp.int8), scale


def make_int8_error_matmul(bit_probs, key, use_pallas: bool = False):
    """Returns matmul(a_f32, b_f32) -> f32 that quantizes, runs the
    error-injected int8 matmul (ref by default; the Pallas kernel opt-in),
    and dequantizes with clipping (the fixed-point requantization step)."""
    from repro.kernels import ref as kref
    cdf = bit_probs_to_cdf(bit_probs)
    counter = [0]

    def mm(a, b):
        counter[0] += 1
        k1, k2 = jax.random.split(jax.random.fold_in(key, counter[0]))
        qa, sa = quantize(a)
        qb, sb = quantize(b)
        u_gate = jax.random.bits(k1, a.shape[:1] + b.shape[1:], jnp.uint32)
        u_bit = jax.random.bits(k2, a.shape[:1] + b.shape[1:], jnp.uint32)
        if use_pallas:
            acc = overscale_matmul(qa, qb, u_gate, u_bit, cdf)
        else:
            acc = kref.overscale_matmul_ref(qa, qb, u_gate, u_bit, cdf)
        # requantize with clipping at the CALIBRATED activation range (the
        # fixed-point pipeline's output scale): a flipped carry/MSB bit
        # saturates instead of exploding — the mechanism behind DNN tolerance.
        clean = jax.lax.dot_general(
            qa.astype(jnp.int32), qb.astype(jnp.int32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
        lim = jnp.quantile(jnp.abs(clean.astype(jnp.float32)), 0.9995)
        out = jnp.clip(acc.astype(jnp.float32), -lim, lim) * sa * sb
        return out

    return mm
