"""ABFT detect/correct over the checksummed over-scaled matmul (§V).

The kernel (``kernels/abft_matmul``) produces the corrupted product C' and
its fused row/column sums; this module compares them with the protected
references (``row_ref = A @ colsum(B)``, ``col_ref = rowsum(A) @ B``) and
repairs what the syndromes localize:

- an XOR flip of bit b in element (i, j) shifts ``rowsum[i]`` and
  ``colsum[j]`` by the same delta (mod 2^32) — a matching nonzero pair
  ``dr[i] == dc[j]`` names the cell, and subtracting the delta restores it
  exactly once the cell's own dot product confirms it;
- multiple flips sharing a row/column alias: their syndromes are detected
  but not uniquely localizable — those remain as *escapes* (the residue the
  ``ErrorTolerant`` accuracy budget is declared against).

:class:`AbftMatmul` is the app-facing drop-in (mirrors
``kernels.overscale_matmul.make_int8_error_matmul``): quantize -> inject ->
detect/correct -> requantize, accumulating detect/correct/escape counters.
:func:`routed_matmuls` installs it on the model layers' matmul hook so a
full inference config (e.g. ``configs/llama3_2_1b``) runs its MLP matmuls
through the checksummed kernel — the accuracy-vs-rail curve machinery of
``examples/overscaling_study.py``.
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as kref
from repro.kernels.abft_matmul import abft_matmul, checksum_refs
from repro.kernels.overscale_matmul import bit_probs_to_cdf, quantize


@dataclass
class AbftCounters:
    """Cumulative SDC ledger of one :class:`AbftMatmul` stream."""
    checked: int = 0    # output elements covered by the checksums
    injected: int = 0   # ground-truth corrupted elements (simulation-only)
    detected: int = 0   # elements the syndromes flagged
    corrected: int = 0  # elements repaired exactly
    escaped: int = 0    # still-wrong elements after repair

    @property
    def detect_rate(self) -> float:
        return self.detected / self.injected if self.injected else 0.0

    @property
    def escape_rate(self) -> float:
        return self.escaped / self.checked if self.checked else 0.0


def detect_and_correct(c, rowsum, colsum, a, b
                       ) -> Tuple[np.ndarray, int, int]:
    """Repair localized flips of ``c`` ~ ``a @ b``; return (c_fixed,
    detected, corrected).  ``rowsum``/``colsum`` are the checksums of ``c``;
    the protected references come from the int8 inputs ``a``, ``b``.  All
    int32, arithmetic wrapping mod 2^32 on both sides of every syndrome.

    A row syndrome ``dr[i]`` that matches exactly one column syndrome
    ``dc[j]`` (and the other way round) names cell (i, j).  A lone flip
    there gives that pair — but so do two flips at (i, j') and (i', j) with
    equal deltas whose row i' and column j' hold further flips.  So a named
    cell is repaired only if subtracting the syndrome restores its own dot
    product ``a[i] . b[:, j]``: a correction never touches a healthy cell.
    """
    row_ref, col_ref = checksum_refs(a, b)
    c = np.asarray(c, np.int32).copy()
    dr = np.subtract(np.asarray(rowsum, np.int32),
                     np.asarray(row_ref, np.int32), dtype=np.int32)
    dc = np.subtract(np.asarray(colsum, np.int32),
                     np.asarray(col_ref, np.int32), dtype=np.int32)
    # corrupted cells announce themselves on both axes; aliasing (several
    # flips sharing a row or column) can hide some — count the larger axis
    detected = int(max(np.count_nonzero(dr), np.count_nonzero(dc)))
    if detected == 0:
        return c, 0, 0
    match = (dr[:, None] == dc[None, :]) & (dr != 0)[:, None]
    # unique row-col pairing only: an ambiguous syndrome must not "repair"
    # a healthy cell
    fix = (match & (match.sum(axis=1) == 1)[:, None]
           & (match.sum(axis=0) == 1)[None, :])
    ii, jj = np.nonzero(fix)
    repaired = np.subtract(c[ii, jj], dr[ii], dtype=np.int32)
    dots = np.einsum("nk,kn->n", np.asarray(a, np.int64)[ii],
                     np.asarray(b, np.int64)[:, jj]).astype(np.int32)
    ok = repaired == dots
    c[ii[ok], jj[ok]] = repaired[ok]
    return c, detected, int(ok.sum())


class AbftMatmul:
    """Drop-in f32 matmul through the ABFT-checksummed over-scaled kernel.

    Mirrors ``make_int8_error_matmul`` (quantize -> inject -> requantize
    with calibrated clipping) with the detect/correct pass in between and
    a :class:`AbftCounters` ledger on the side.  ``use_pallas`` selects the
    fused Pallas kernel (compiled on a TPU, interpreted on the CPU) over the
    jnp oracle.
    """

    def __init__(self, bit_probs, key, use_pallas: bool = False):
        self.cdf = bit_probs_to_cdf(bit_probs)
        self.key = key
        self.use_pallas = use_pallas
        self.counters = AbftCounters()
        self._n = 0

    def __call__(self, a, b):
        self._n += 1
        k1, k2 = jax.random.split(jax.random.fold_in(self.key, self._n))
        qa, sa = quantize(a)
        qb, sb = quantize(b)
        shape = a.shape[:1] + b.shape[1:]
        u_gate = jax.random.bits(k1, shape, jnp.uint32)
        u_bit = jax.random.bits(k2, shape, jnp.uint32)
        if self.use_pallas:
            c, rs, cs = abft_matmul(qa, qb, u_gate, u_bit, self.cdf)
        else:
            c, rs, cs = kref.abft_matmul_ref(qa, qb, u_gate, u_bit, self.cdf)
        fixed, detected, corrected = detect_and_correct(c, rs, cs, qa, qb)
        # simulation ground truth: the clean product (already needed for
        # the requantization clip limit) exposes injections and escapes
        clean = np.asarray(jax.lax.dot_general(
            qa.astype(jnp.int32), qb.astype(jnp.int32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32))
        self.counters.checked += int(fixed.size)
        self.counters.injected += int(np.count_nonzero(np.asarray(c) != clean))
        self.counters.detected += detected
        self.counters.corrected += corrected
        self.counters.escaped += int(np.count_nonzero(fixed != clean))
        lim = np.quantile(np.abs(clean.astype(np.float32)), 0.9995)
        out = np.clip(fixed.astype(np.float32), -lim, lim) \
            * float(sa) * float(sb)
        return jnp.asarray(out)


@contextmanager
def routed_matmuls(mm):
    """Route the model layers' dense matmuls (``models.layers.matmul``)
    through ``mm`` for the duration of the block — non-jitted evaluation
    only (the ABFT wrapper keeps host-side counters)."""
    from repro.models import layers
    prev = layers.MATMUL
    layers.MATMUL = mm
    try:
        yield mm
    finally:
        layers.MATMUL = prev


def topk_agreement(logits, ref_logits, k: int = 1) -> float:
    """Accuracy proxy for the rail curves: fraction of positions whose
    top-k next-token sets agree with the clean-rail reference."""
    a = np.asarray(logits, np.float32).reshape(-1, logits.shape[-1])
    b = np.asarray(ref_logits, np.float32).reshape(-1, ref_logits.shape[-1])
    ta = np.argsort(-a, axis=-1)[:, :k]
    tb = np.argsort(-b, axis=-1)[:, :k]
    agree = [len(set(ta[i]) & set(tb[i])) / k for i in range(ta.shape[0])]
    return float(np.mean(agree))
