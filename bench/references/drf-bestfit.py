"""Plain reference of one BF-DRF epoch: DRF picks the framework, best fit
picks the machine.

Written from the definitions, in float64 numpy, independent of the code
under test (it imports nothing of ``repro``); the contract of a reference
file is in ``bench/reference.py``'s docstring.

* a row may take one more executor on a column when it wants more and its
  demand fits the column's free resources (``EPS`` absorbs rounding);
* the row: among the rows with a feasible column, the least DRF score, its
  executors times its dominant demand share of the pooled capacity, over
  its weight;
* the column: among that row's feasible columns, the least best-fit score
  of its free vector ``a`` against the row's demand ``d``; under
  ``cosine``, ``1 - a.d / (|a| |d|)`` (1 where either is zero);
* ties, scores within float64 rounding of the least, go to the lowest
  index, for the row and for the column;
* the epoch ends when no pair is feasible.

The metric is ``cosine``, the allocator's default and the only one the
device serves.  Each step touches one row and one column: per-row counts
of feasible columns decide which rows are in play, and a grant refits only
its column, so a round of ~10^4 grants over 16384 columns replays in
seconds.
"""
from __future__ import annotations

import numpy as np

EPS = 1e-9            # feasibility slack on free resources


def _tie(m: float) -> float:
    """Scores up to this value are tied with the least score ``m``."""
    return m + 1e-9 * abs(m) + 1e-12


def _cosine(free: np.ndarray, d: np.ndarray) -> np.ndarray:
    """``1 - cos`` of each free vector (rows of ``free``) with ``d``."""
    den = np.sqrt(np.einsum("jr,jr->j", free, free) * float(d @ d))
    num = free @ d
    out = np.ones(len(free))
    nz = den > 0
    out[nz] = 1.0 - num[nz] / den[nz]
    return out


def epoch(config, *, D, tot, wanted, phi, free, ctot=None, rng=None,
          score_round=None) -> list:
    """The reference grant sequence ``[(row, column), ...]`` of one epoch
    (inputs as in ``bench/reference.py``'s ``epoch``)."""
    rnd = score_round or (lambda x: x)
    D = np.asarray(D, np.float64)
    tot = np.array(tot, np.float64)
    wanted = np.asarray(wanted, np.float64)
    phi = np.asarray(phi, np.float64)
    free = np.array(free, np.float64)
    W, J = len(D), len(free)
    if W == 0 or J == 0:
        return []
    wants = tot < wanted
    feas = wants[:, None] & np.ones((W, J), bool)
    for r in range(D.shape[1]):
        feas &= D[:, r, None] <= free[None, :, r] + EPS
    cnt = feas.sum(axis=1)
    unit = (D / np.maximum(np.asarray(ctot, np.float64), 1e-30)).max(axis=1)
    s = rnd(tot * unit / phi)
    seq = []
    while True:
        live = cnt > 0
        if not live.any():
            return seq
        row = np.where(live, s, np.inf)
        n = int(np.argmax(row <= _tie(row.min())))
        cols = np.flatnonzero(feas[n])
        bf = rnd(_cosine(free[cols], D[n]))
        j = int(cols[np.argmax(bf <= _tie(bf.min()))])
        seq.append((n, j))
        free[j] -= D[n]
        tot[n] += 1
        wants[n] = tot[n] < wanted[n]
        s[n] = rnd(tot[n] * unit[n] / phi[n])
        # column j: its free resources fell
        col = wants & (D <= free[j] + EPS).all(axis=1)
        cnt += col.astype(np.int64) - feas[:, j]
        feas[:, j] = col
        # row n: it may want no more
        if not wants[n]:
            feas[n] = False
            cnt[n] = 0
