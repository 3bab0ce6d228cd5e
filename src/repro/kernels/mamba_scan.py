"""Pallas TPU kernel: Mamba2 SSD chunked scan (intra-chunk + state carry).

Grid = (num_chunks,) iterated sequentially (TPU grid order) with the running
inter-chunk state (H, P, N) in VMEM scratch — the recurrence never leaves
VMEM. Per chunk the kernel computes the quadratic intra-chunk term, the
read-out from the carried state, and the state update, all in fp32.

Block tiling per chunk c: x (Q, H, P), dt (Q, H), B/C (Q, H, N) — for the
assigned mamba2-780m (Q=256, H=48, P=64, N=128) the chunk working set is
~3 MB, comfortably VMEM-resident; heads can be split over an extra grid dim
(or sharded by TP) for larger models.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, y_ref, state_ref, *,
            q: int, n_chunks: int):
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)  # (Q,H,P)
    dt = dt_ref[...].astype(jnp.float32)  # (Q,H)
    A = A_ref[...].astype(jnp.float32)  # (H,)
    B = B_ref[...].astype(jnp.float32)  # (Q,H,N)
    C = C_ref[...].astype(jnp.float32)  # (Q,H,N)

    dA = dt * A[None, :]  # (Q,H)
    cs = jnp.cumsum(dA, axis=0)  # (Q,H)
    # intra-chunk: L[i,j] = exp(cs_i - cs_j) for i>=j ; score G = C_i . B_j
    diff = cs[:, None, :] - cs[None, :, :]  # (Q,Q,H)
    mask = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(mask[..., None], jnp.exp(diff), 0.0)  # (Q,Q,H)
    G = jnp.einsum("ihn,jhn->ijh", C, B)  # (Q,Q,H)
    M = G * L * dt[None, :, :]  # weight on x_j
    y = jnp.einsum("ijh,jhp->ihp", M, x)
    # read-out from carried state
    in_decay = jnp.exp(cs)  # (Q,H)
    y += jnp.einsum("ihn,hpn,ih->ihp", C, state_ref[...], in_decay)
    y_ref[...] = y.astype(y_ref.dtype)
    # state update
    tot = jnp.exp(cs[-1])  # (H,)
    decay_to_end = jnp.exp(cs[-1][None, :] - cs)  # (Q,H)
    new_state = (state_ref[...] * tot[:, None, None]
                 + jnp.einsum("qh,qhn,qhp->hpn", decay_to_end * dt, B, x))
    state_ref[...] = new_state


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba_scan(xh, dt, A, B, C, *, chunk: int = 256,
               interpret: Optional[bool] = None):
    """Single-batch SSD scan. xh:(S,H,P) dt:(S,H) A:(H,) B,C:(S,H,N) -> y.

    vmap over batch. Returns y:(S,H,P) (fp32 math, xh.dtype out).
    """
    interpret = resolve_interpret(interpret)
    S, H, P = xh.shape
    N = B.shape[-1]
    q = min(chunk, S)
    assert S % q == 0
    nc = S // q
    return pl.pallas_call(
        functools.partial(_kernel, q=q, n_chunks=nc),
        grid=(nc,),
        in_specs=[
            pl.BlockSpec((q, H, P), lambda c: (c, 0, 0)),
            pl.BlockSpec((q, H), lambda c: (c, 0)),
            pl.BlockSpec((H,), lambda c: (0,)),
            pl.BlockSpec((q, H, N), lambda c: (c, 0, 0)),
            pl.BlockSpec((q, H, N), lambda c: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((q, H, P), lambda c: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, H, P), xh.dtype),
        scratch_shapes=[pltpu.VMEM((H, P, N), jnp.float32)],
        interpret=interpret,
    )(xh, dt, A, B, C)
