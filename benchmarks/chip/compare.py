"""The comparison that decides a serving cell's ``correct``.

Once the window has closed, a sample of the finished requests, drawn from
the seed and always holding the one with the most served tokens, goes
through the configuration's plain reference: one forward pass over each
prompt followed by its served tokens.  At each served position the gap is
how far the served token's reference logit lies below the reference's best
there; the number compared is the widest gap over the sample.  Greedy
serving at the stated precision keeps it near zero; a wrong token, or a
computation in a lower precision, widens it.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np


def pick(finished: Sequence[Tuple[np.ndarray, list]], seed: int,
         tokens: int) -> List[Tuple[np.ndarray, list]]:
    """The longest finished request, then others in an order drawn from the
    seed, until ``tokens`` served tokens are in the sample."""
    if not finished:
        return []
    order = list(np.random.default_rng(seed).permutation(len(finished)))
    longest = max(range(len(finished)),
                  key=lambda i: (len(finished[i][1]), len(finished[i][0])))
    order.remove(longest)
    out, n = [], 0
    for i in [longest] + order:
        out.append(finished[i])
        n += len(finished[i][1])
        if n >= tokens:
            break
    return out


def served_gaps(ref, w, src: dict, prompt, out, length: int,
                mm=None) -> np.ndarray:
    """Per served token: reference max logit minus the served token's.

    ``mm`` computes the ranking in another precision (the control): then
    the token that precision ranks first is read against the reference, in
    place of the served one."""
    seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
    P, M = len(prompt), len(out)
    toks = np.zeros(length, np.int32)
    toks[:len(seq)] = seq
    logits = np.asarray(ref.logits(w, toks, src))
    rows = logits[P - 1:P - 1 + M]
    if mm is not None:
        chosen = np.asarray(ref.logits(w, toks, src, mm=mm))[
            P - 1:P - 1 + M].argmax(-1)
    else:
        chosen = np.asarray(out)
    return rows.max(-1) - rows[np.arange(M), chosen]


def max_served_gap(ref, w, src: dict, sample, length: int,
                   mm=None) -> Optional[float]:
    gaps = [served_gaps(ref, w, src, p, o, length, mm).max()
            for p, o in sample]
    return float(max(gaps)) if gaps else None
