"""Steady-state RC thermal grid solver (the HotSpot-6.0 analogue).

The die is the netlist's (m × n) tile grid. Each tile couples laterally to its
4 neighbours (silicon spreading conductance) and vertically to ambient through
the package (convective conductance). Steady state solves

    (G_v + sum_nbr G_lat) T_ij - G_lat * sum_nbr T_nbr = P_ij + G_v * T_amb

Calibration follows the paper: the convective resistance is tuned so a total
power of 1 W raises the (mean) junction temperature by theta_JA — 2 degC/W for
high-end packages (Virtex-7/Stratix-V class), 12 degC/W for mid-size devices
with still air (Spartan/Artix class).

Solver tiers (``ThermalConfig.solver``; DESIGN.md "Thermal solver
hierarchy"):

- ``"multigrid"`` (default) — geometric V-cycles on the 5-point conductance
  stencil: red-black Gauss-Seidel smoothing, full-weighting (block-sum)
  restriction of the extensive residual, bilinear prolongation of the
  coarse correction, and a dense direct solve (precomputed inverse) once
  the level fits ``coarse_cells`` (grids that small — e.g. the 16x16 pod —
  skip iteration entirely: ONE constant-matrix multiply, exact). Cold
  starts descend full-multigrid (coarsest solve prolongated up, one
  V-cycle per level). Convergence is checked ONCE per V-cycle (a cycle is
  ~4*n_smooth fused sweeps), and each cycle contracts the error by ~10x,
  so the loop runs a handful of cycles where Jacobi ran thousands of
  sweeps (its contraction is 1/(1 + 1/(4*spreading)) per sweep — ~0.99 for
  the FPGA packages — with a global reduce after every one).
- ``"jacobi"`` — the seed relaxation, kept as the parity oracle, but with
  *chunked* convergence checks: ``check_every`` fused sweeps between
  |dT|_inf reduces (``check_every=1`` is bit-for-bit the seed loop).

Both tiers accept an explicit ``T0`` warm start (the fixed-point solver
passes the previous iteration's field; the control plane passes the last
converged/applied field) and stop on the same criterion — the per-sweep
(resp. per-cycle) |dT|_inf dropping under ``tol`` — so the steady state is
tier-independent at the configured tolerance.

The smoother dispatches on backend: the fused-K-sweep Pallas kernel
(``kernels/thermal_stencil``, red-black phase) on TPU, pure jnp elsewhere
(``ThermalConfig.backend`` overrides). Everything traces under jit and vmap:
level shapes are static, the per-level diagonals and the coarse-grid inverse
are numpy constants baked in at trace time.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import resolve_interpret


@dataclass(frozen=True)
class ThermalConfig:
    theta_ja: float = 2.0  # degC/W effective junction-to-ambient resistance
    spreading: float = 25.0  # lateral/vertical conductance ratio (die spread)
    tol: float = 5e-5  # convergence |dT|_inf per sweep/cycle [degC]
    max_iters: int = 50_000  # sweep budget (jacobi tier)
    solver: str = "multigrid"  # "multigrid" | "jacobi"
    backend: str = "auto"  # smoother: "auto" (pallas on TPU) | "pallas" | "jnp"
    n_smooth: int = 1  # RB-GS pre- and post-smoothing sweeps per V-cycle
    coarse_cells: int = 512  # direct-solve at <= this many cells: the whole
    # 16x16 pod (and the coarse tail of every V-cycle) is ONE precomputed
    # A^-1 matmul — exact, while-loop-free, vmap-friendly
    max_cycles: int = 200  # V-cycle budget (multigrid tier)
    check_every: int = 32  # fused sweeps between reduces (jacobi tier)


def conductances(m: int, n: int, tc: ThermalConfig) -> Tuple[float, float]:
    """(G_v per tile [W/degC], G_lat between neighbours)."""
    g_v = 1.0 / (tc.theta_ja * m * n)
    g_lat = g_v * tc.spreading
    return g_v, g_lat


def _nbr_sum(T):
    up = jnp.pad(T[1:, :], ((0, 1), (0, 0)))
    dn = jnp.pad(T[:-1, :], ((1, 0), (0, 0)))
    lf = jnp.pad(T[:, 1:], ((0, 0), (0, 1)))
    rt = jnp.pad(T[:, :-1], ((0, 0), (1, 0)))
    return up + dn + lf + rt


def _diag_np(gv_map: np.ndarray, g_lat: float) -> np.ndarray:
    m, n = gv_map.shape
    nbrc = np.full((m, n), 4.0)
    nbrc[0, :] -= 1
    nbrc[-1, :] -= 1
    nbrc[:, 0] -= 1
    nbrc[:, -1] -= 1
    return gv_map + g_lat * nbrc


def _interp_weights_np(mm: int, mc: int) -> np.ndarray:
    """1D cell-centered linear interpolation matrix (mm x mc).

    Coarse cell j covers fine cells [2j, min(2j+1, mm-1)] (the trailing
    slab of an odd dimension covers one); each fine center interpolates
    between the bracketing coarse-span centers, clamped at the edges.
    """
    centers = np.array([(2 * j + min(2 * j + 1, mm - 1) + 1.0) / 2.0
                        for j in range(mc)])
    W = np.zeros((mm, mc))
    for i in range(mm):
        xi = i + 0.5
        j = int(np.searchsorted(centers, xi))
        if j == 0:
            W[i, 0] = 1.0
        elif j >= mc:
            W[i, mc - 1] = 1.0
        else:
            w = (xi - centers[j - 1]) / (centers[j] - centers[j - 1])
            W[i, j - 1], W[i, j] = 1.0 - w, w
    return W


@lru_cache(maxsize=64)
def _plan_levels(m: int, n: int, g_v: float, g_lat: float,
                 coarse_cells: int):
    """Static multigrid hierarchy (numpy constants baked in at trace time):
    per-level dims + stencil diagonal + prolongation matrices, and the dense
    inverse of the coarsest-level operator.

    Rediscretization: a coarse cell aggregates its fine cells' vertical
    conductances (block sum — exact for odd trailing slabs), while the
    lateral conductance between coarse cells stays ``g_lat`` (interface
    doubles, path length doubles). The restricted residual is extensive
    (W per cell), so restriction is the block SUM — full weighting times
    the 2x2 cell area — and every term of the coarse equation scales
    consistently.
    """
    levels = []
    gv = np.full((m, n), g_v, np.float64)
    while True:
        mm, nn = gv.shape
        levels.append([mm, nn, _diag_np(gv, g_lat).astype(np.float32),
                       None, None])
        if mm * nn <= coarse_cells or (mm == 1 and nn == 1):
            break
        mc, nc = (mm + 1) // 2, (nn + 1) // 2
        levels[-1][3] = _interp_weights_np(mm, mc).astype(np.float32)
        levels[-1][4] = _interp_weights_np(nn, nc).astype(np.float32)
        pad = np.zeros((2 * mc, 2 * nc))
        pad[:mm, :nn] = gv
        gv = pad.reshape(mc, 2, nc, 2).sum(axis=(1, 3))

    mm, nn, diag_c = levels[-1][:3]
    A = np.diag(diag_c.reshape(-1).astype(np.float64))
    idx = np.arange(mm * nn).reshape(mm, nn)
    for di, dj in ((1, 0), (0, 1)):
        src = idx[:mm - di, :nn - dj].reshape(-1)
        dst = idx[di:, dj:].reshape(-1)
        A[src, dst] -= g_lat
        A[dst, src] -= g_lat
    A_inv = np.linalg.inv(A).astype(np.float32)
    return tuple(tuple(lv) for lv in levels), A_inv


def _use_pallas(tc: ThermalConfig) -> bool:
    if tc.backend == "auto":  # the compiled kernel where there is one
        return not resolve_interpret(None)
    return tc.backend == "pallas"


def _smooth(T, b, diag, g_lat: float, sweeps: int, pallas: bool):
    """``sweeps`` red-black Gauss-Seidel sweeps (red first)."""
    if pallas:
        from repro.kernels.thermal_stencil import thermal_stencil
        return thermal_stencil(T, b, diag, g_lat=g_lat, g_v_tamb=0.0,
                               iters=sweeps, phase=0)
    m, n = T.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (m, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (m, n), 1)
    par = (row + col) % 2
    for _ in range(sweeps):
        for p in (0, 1):
            T = jnp.where(par == p, (b + g_lat * _nbr_sum(T)) / diag, T)
    return T


def _jacobi_sweeps(T, b, diag, g_lat: float, sweeps: int, pallas: bool):
    if pallas:
        from repro.kernels.thermal_stencil import thermal_stencil
        return thermal_stencil(T, b, diag, g_lat=g_lat, g_v_tamb=0.0,
                               iters=sweeps, phase=None)
    return jax.lax.fori_loop(
        0, sweeps, lambda _, t: (b + g_lat * _nbr_sum(t)) / diag, T)


def _restrict(r, mc: int, nc: int):
    """Full-weighting of the extensive residual: 2x2 block sums (zero-padded
    on odd trailing edges, where the coarse cell covers fewer fine cells)."""
    m, n = r.shape
    r = jnp.pad(r, ((0, 2 * mc - m), (0, 2 * nc - n)))
    return r.reshape(mc, 2, nc, 2).sum(axis=(1, 3))


def _solve_multigrid(b, T0, m: int, n: int, g_v: float, g_lat: float,
                     tc: ThermalConfig):
    levels, A_inv = _plan_levels(m, n, g_v, g_lat, int(tc.coarse_cells))
    A_inv = jnp.asarray(A_inv)
    diags = [jnp.asarray(lv[2]) for lv in levels]
    # bilinear prolongation as two small dense matmuls (constant weights)
    Ws = [(jnp.asarray(lv[3]), jnp.asarray(lv[4]))
          for lv in levels if lv[3] is not None]
    pallas = _use_pallas(tc)

    def coarse_solve(bc, mm, nn):
        return (A_inv @ bc.reshape(-1)).reshape(mm, nn)

    def scaled_residual(T):
        """max |r| / diag — exactly the |dT|_inf one Jacobi sweep would
        apply at T, i.e. the seed solver's stopping metric."""
        r = b - (diags[0] * T - g_lat * _nbr_sum(T))
        return jnp.max(jnp.abs(r) / diags[0])

    def vcycle(lvl, T, b_l):
        mm, nn = levels[lvl][:2]
        diag = diags[lvl]
        if lvl == len(levels) - 1:
            return coarse_solve(b_l, mm, nn)
        T = _smooth(T, b_l, diag, g_lat, tc.n_smooth, pallas)
        r = b_l - (diag * T - g_lat * _nbr_sum(T))
        mc, nc = levels[lvl + 1][:2]
        e = vcycle(lvl + 1, jnp.zeros((mc, nc), jnp.float32),
                   _restrict(r, mc, nc))
        Wr, Wc = Ws[lvl]
        T = T + Wr @ e @ Wc.T  # cell-centered bilinear prolongation
        return _smooth(T, b_l, diag, g_lat, tc.n_smooth, pallas)

    if len(levels) == 1:  # the whole grid fits the direct tier: exact solve
        return coarse_solve(b, m, n)

    if T0 is None:
        # full-multigrid cold start: solve the restricted problem on the
        # coarsest level exactly, prolongate up with one V-cycle per level
        # — ~1.3 cycle-equivalents that land near truncation error, where
        # an analytic estimate would cost 2-3 extra fine cycles
        bs = [b]
        for lvl in range(len(levels) - 1):
            mc, nc = levels[lvl + 1][:2]
            bs.append(_restrict(bs[-1], mc, nc))
        T0 = coarse_solve(bs[-1], *levels[-1][:2])
        for lvl in range(len(levels) - 2, -1, -1):
            Wr, Wc = Ws[lvl]
            T0 = vcycle(lvl, Wr @ T0 @ Wc.T, bs[lvl])

    def body(state):
        T, _, s_prev, i = state
        T = vcycle(0, T, b)
        return T, s_prev, scaled_residual(T), i + 1

    def cond(state):
        # stop when converged under tol OR stalled at the f32 residual
        # floor (each cycle contracts the true error ~10x, so a cycle that
        # no longer shrinks the residual has nothing left to converge)
        _, s_prev, s, i = state
        return (s > tc.tol) & (s < 0.9 * s_prev) & (i < tc.max_cycles)

    s0 = scaled_residual(T0)  # 0 cycles for an already-converged warm start
    T, _, _, _ = jax.lax.while_loop(cond, body,
                                    (T0, jnp.float32(jnp.inf), s0, 0))
    return T


def _solve_jacobi(b, T0, m: int, n: int, g_v: float, g_lat: float,
                  tc: ThermalConfig):
    diag = jnp.asarray(_diag_np(np.full((m, n), g_v), g_lat), jnp.float32)
    pallas = _use_pallas(tc)
    K = max(int(tc.check_every), 1)

    def body(state):
        T, _, i = state
        # K-1 fused sweeps, then one measured sweep: the reduce compares
        # consecutive sweeps — the seed criterion at chunk granularity
        T_mid = _jacobi_sweeps(T, b, diag, g_lat, K - 1, pallas)
        T_new = _jacobi_sweeps(T_mid, b, diag, g_lat, 1, pallas)
        return T_new, jnp.max(jnp.abs(T_new - T_mid)), i + K

    def cond(state):
        _, err, i = state
        return (err > tc.tol) & (i < tc.max_iters)

    T, _, _ = jax.lax.while_loop(cond, body, (T0, jnp.inf, 0))
    return T


@partial(jax.jit, static_argnums=(1, 2, 4))
def solve(power_mw, m: int, n: int, t_amb, tc: ThermalConfig = ThermalConfig(),
          T0=None):
    """power_mw: (m*n,) per-tile power in mW -> (m*n,) temperatures [degC].

    ``T0`` (flat (m*n,) or (m,n)) warm-starts the iteration; every caller
    sitting inside a fixed point should pass its previous field. The default
    is the seed's analytic estimate (ambient + half the vertical rise).
    """
    g_v, g_lat = conductances(m, n, tc)
    P = power_mw.reshape(m, n).astype(jnp.float32) * 1e-3  # W
    t_amb = jnp.asarray(t_amb, jnp.float32)
    b = P + g_v * t_amb

    if T0 is not None:
        T0 = jnp.asarray(T0, jnp.float32).reshape(m, n)

    if tc.solver == "multigrid":
        # a cold multigrid start (T0=None) uses the full-multigrid descent
        T = _solve_multigrid(b, T0, m, n, g_v, g_lat, tc)
    elif tc.solver == "jacobi":
        if T0 is None:  # the seed's analytic warm start
            T0 = jnp.full((m, n), t_amb) + P / g_v * 0.5
        T = _solve_jacobi(b, T0, m, n, g_v, g_lat, tc)
    else:
        raise ValueError(f"unknown thermal solver {tc.solver!r}")
    return T.reshape(-1)


def steady_stats(T_tiles, m: int, n: int):
    return {"mean": jnp.mean(T_tiles), "max": jnp.max(T_tiles),
            "min": jnp.min(T_tiles)}
