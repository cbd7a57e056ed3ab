"""Rank-Biased Overlap (Webber, Moffat, Zobel, TOIS 2010), extrapolated.

The paper's accuracy metric, kept with the benchmark so that the yardstick
cannot move with the program: RBO_ext over prefix depth k with
persistence ``p``, ties broken by vertex id, active vertices only.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def rbo_extrapolated(s: Sequence[int], t: Sequence[int],
                     p: float = 0.99) -> float:
    """RBO_ext of two rankings of distinct ids, best first:
    ``(1-p) * sum_{d<k} A_d p^(d-1) + A_k p^(k-1)`` with ``A_d`` the
    overlap of the two depth-d prefixes over d."""
    s, t = list(s), list(t)
    k = max(len(s), len(t))
    if k == 0:
        return 1.0
    if min(len(s), len(t)) == 0:
        return 0.0
    seen_s: set = set()
    seen_t: set = set()
    overlap = 0
    weighted = 0.0
    weight = 1.0
    a_d = 0.0
    for d in range(1, k + 1):
        e_s = s[d - 1] if d <= len(s) else None
        e_t = t[d - 1] if d <= len(t) else None
        if e_s is not None and e_s == e_t:
            overlap += 1
        else:
            if e_s is not None and e_s in seen_t:
                overlap += 1
            if e_t is not None and e_t in seen_s:
                overlap += 1
        if e_s is not None:
            seen_s.add(e_s)
        if e_t is not None:
            seen_t.add(e_t)
        a_d = overlap / d
        if d < k:
            weighted += a_d * weight
        weight *= p
    return float((1.0 - p) * weighted + a_d * p ** (k - 1))


def rbo_from_scores(a: np.ndarray, b: np.ndarray, *, depth: int,
                    p: float = 0.99,
                    active: Optional[np.ndarray] = None) -> float:
    """RBO_ext of the top ``depth`` of two score vectors (descending,
    ties by id), restricted to ``active``."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    idx = (np.nonzero(active)[0] if active is not None
           else np.arange(a.shape[0]))
    top_a = idx[np.lexsort((idx, -a[idx]))][:depth]
    top_b = idx[np.lexsort((idx, -b[idx]))][:depth]
    return rbo_extrapolated(top_a.tolist(), top_b.tolist(), p=p)
