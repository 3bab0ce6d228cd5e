"""Pallas TPU kernel: fused multi-sweep thermal stencil (Jacobi or red-black).

Hot loop of the HotSpot-style steady-state solver (core/thermal.py). The
FPGA/TPU thermal grids are small (92x92 .. 256x256 -> <= 256 KB fp32), so the
TPU-native tiling is: keep the WHOLE grid resident in VMEM and fuse K sweeps
inside one ``pallas_call`` (a ``fori_loop`` in-kernel), cutting HBM<->VMEM
round-trips by K versus K separate XLA iterations. This is the
hardware-adaptation analogue of blocking for cache: VMEM (~16 MB) dwarfs the
working set, so the bottleneck is launch/HBM overhead, not compute.

Two sweep flavours share the kernel body:

- ``phase=None`` — K Jacobi sweeps (the legacy fused relaxation);
- ``phase=0|1``  — K red-black Gauss-Seidel sweeps starting on that
  checkerboard colour: the multigrid smoother of ``core.thermal``. Each
  sweep updates one colour from the *freshly written* other colour, which
  is what gives RB-GS its 2x Jacobi smoothing rate; the colour masks are
  2D ``broadcasted_iota`` parities, which lower to vector ops on TPU.

Block layout: grid=(), whole-array BlockSpecs in VMEM; the neighbour sum is
computed with in-kernel shifts (jnp.pad/slice lower to vector ops on TPU).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _kernel(T_ref, P_ref, diag_ref, o_ref, *, g_lat: float, g_v_tamb: float,
            iters: int, phase: Optional[int]):
    P = P_ref[...]
    diag = diag_ref[...]

    def nbr(T):
        up = jnp.pad(T[1:, :], ((0, 1), (0, 0)))
        dn = jnp.pad(T[:-1, :], ((1, 0), (0, 0)))
        lf = jnp.pad(T[:, 1:], ((0, 0), (0, 1)))
        rt = jnp.pad(T[:, :-1], ((0, 0), (1, 0)))
        return up + dn + lf + rt

    if phase is None:
        def body(_, T):
            return (P + g_v_tamb + g_lat * nbr(T)) / diag
    else:
        m, n = P_ref.shape
        row = jax.lax.broadcasted_iota(jnp.int32, (m, n), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (m, n), 1)
        par = (row + col) % 2

        def body(_, T):
            for p in (phase, 1 - phase):
                T = jnp.where(par == p,
                              (P + g_v_tamb + g_lat * nbr(T)) / diag, T)
            return T

    o_ref[...] = jax.lax.fori_loop(0, iters, body, T_ref[...])


@functools.partial(jax.jit, static_argnames=("iters", "g_lat", "g_v_tamb",
                                             "phase", "interpret"))
def thermal_stencil(T, P, diag, *, g_lat: float, g_v_tamb: float,
                    iters: int = 64, phase: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """K fused sweeps. T,P,diag: (m,n) fp32 -> (m,n) fp32.

    ``phase=None`` runs Jacobi sweeps; ``phase=0|1`` runs red-black
    Gauss-Seidel sweeps starting on that colour.  ``interpret`` follows
    :func:`repro.kernels.resolve_interpret`.
    """
    interpret = resolve_interpret(interpret)
    m, n = T.shape
    spec = pl.BlockSpec((m, n), lambda: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, g_lat=float(g_lat),
                          g_v_tamb=float(g_v_tamb), iters=iters, phase=phase),
        grid=(),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(T.astype(jnp.float32), P.astype(jnp.float32), diag.astype(jnp.float32))
