"""Plain reference of one allocation epoch (progressive filling).

Written from the paper's definitions, in float64 numpy, and independent of
the code under test: it imports nothing of ``repro`` and is given only the
epoch's inputs as the benchmark itself tracked them.

Semantics of one epoch over the frameworks that still want executors
(rows, in name order) and the machines (columns, in name order):

* a row may take one more executor on a column when it wants more and its
  demand fits the column's free resources (``eps`` absorbs rounding);
* scores are minimized: DRF scores a row by its executors times its
  dominant demand share of the pooled capacity, over its weight; rPS-DSF
  scores a (row, column) pair by the row's executors over its weight times
  its dominant demand share of the column's residual (= free) capacity;
* ``pooled``: the feasible pair with the least score wins; ``rrr``: columns
  are visited in a random order, re-drawn each round (the first order at
  the epoch's start, a new one whenever a round ends), and the visited
  column's feasible row with the least score wins;
* ties, scores within float64 rounding of the least, go to the lowest
  (row, column) index;
* the epoch ends when no pair is feasible.

``score_round`` rounds every score (the control holds them in bfloat16).

A configuration whose criterion and server policy this file does not cover
brings its own reference as ``bench/references/<criterion>-<server_policy>.py``
(found by ``bench.spec.reference``; such a file also wins over this one).
It defines

    epoch(config, *, D, tot, wanted, phi, free, ctot, rng, score_round)
        -> [(row, column), ...]

with the inputs of :func:`epoch` below, as float64 numpy arrays, and
``config`` the configuration file as loaded (for settings such as a
best-fit metric).  It imports nothing of ``repro``, and passes every score
through ``score_round`` when that is given, so that the control still
breaks it.  :func:`config_epoch` is this file's epoch in that form.
"""
from __future__ import annotations

import numpy as np

EPS = 1e-9            # feasibility slack on free resources
_BIG = 1e18           # dominant share on a column with no capacity left


def _tie(m: float) -> float:
    """Scores up to this value are tied with the least score ``m``."""
    return m + 1e-9 * abs(m) + 1e-12


def _fits(D: np.ndarray, free: np.ndarray) -> np.ndarray:
    """(W, J') bool: each row's demand fits each column's free vector."""
    ok = np.ones((D.shape[0], free.shape[0]), bool)
    for r in range(D.shape[1]):
        ok &= D[:, r, None] <= free[None, :, r] + EPS
    return ok


def _dominant(D: np.ndarray, cap: np.ndarray) -> np.ndarray:
    """(W, J') max over resources of demand / capacity."""
    out = np.zeros((D.shape[0], cap.shape[0]))
    for r in range(D.shape[1]):
        c = cap[None, :, r]
        frac = D[:, r, None] / np.where(c > 1e-12, c, 1e-30)
        frac = np.where((c <= 1e-12) & (D[:, r, None] > 0), _BIG, frac)
        np.maximum(out, frac, out=out)
    return out


#: the (criterion, server policy) pairs :func:`epoch` covers
COVERED = (("rpsdsf", "pooled"), ("drf", "rrr"), ("rpsdsf", "rrr"))


def config_epoch(config: dict, **inputs) -> list:
    """:func:`epoch` for ``config``'s criterion and server policy, in the
    form of a reference file."""
    return epoch(config["criterion"], config["server_policy"], **inputs)


def epoch(criterion: str, policy: str, *, D, tot, wanted, phi, free,
          ctot=None, rng=None, score_round=None) -> list:
    """The reference grant sequence ``[(row, column), ...]`` of one epoch.

    ``D`` (W, R) demands, ``tot`` (W,) executors held, ``wanted`` (W,)
    executors wanted, ``phi`` (W,) weights, ``free`` (J, R) free resources;
    ``ctot`` (R,) pooled capacity (DRF); ``rng`` a numpy Generator at the
    epoch's stream position (RRR)."""
    if (criterion, policy) not in COVERED:
        raise ValueError(f"no reference for {criterion}/{policy}")
    rnd = score_round or (lambda x: x)
    D = np.asarray(D, np.float64)
    state = dict(tot=np.array(tot, np.float64),
                 wanted=np.asarray(wanted, np.float64),
                 phi=np.asarray(phi, np.float64),
                 free=np.array(free, np.float64))
    if len(D) == 0 or len(state["free"]) == 0:
        return []
    if policy == "pooled":
        return _pooled_pairwise(D, rnd, **state)
    if rng is None:
        raise ValueError("an rrr epoch needs the allocator's rng state")
    return _rrr(criterion, D, rnd, ctot=ctot, rng=rng, **state)


def _column(D: np.ndarray, wants, free_j: np.ndarray):
    """(fits, dominant share) of every row on one column: the same
    arithmetic as :func:`_fits` and :func:`_dominant`, for one column."""
    fits = wants.copy()
    dom = np.zeros(len(D))
    for r in range(D.shape[1]):
        c = free_j[r]
        fits &= D[:, r] <= c + EPS
        if c > 1e-12:
            frac = D[:, r] / c
        else:
            frac = np.where(D[:, r] > 0, _BIG, D[:, r] / 1e-30)
        np.maximum(dom, frac, out=dom)
    return fits, dom


def _pooled_pairwise(D, rnd, *, tot, wanted, phi, free) -> list:
    """rPS-DSF, pooled: the least-score feasible pair, kept as per-row
    minima with a count of the columns at each minimum.  Scores only rise
    and feasibility only shrinks within an epoch, so a row is rescanned
    only when its last column at the minimum moves off it."""
    wants = tot < wanted
    feas = _fits(D, free) & wants[:, None]
    dom = _dominant(D, free)
    s = rnd((tot / phi)[:, None] * dom)
    rowmin = np.empty(len(D))
    cnt = np.empty(len(D), np.int64)

    def rescan(rows):
        sub = np.where(feas[rows], s[rows], np.inf)
        rowmin[rows] = sub.min(axis=1)
        cnt[rows] = (sub == rowmin[rows, None]).sum(axis=1)

    def rescan_row(n):
        sub = np.where(feas[n], s[n], np.inf)
        rowmin[n] = sub.min()
        cnt[n] = np.count_nonzero(sub == rowmin[n])

    rescan(np.arange(len(D)))
    seq = []
    while True:
        m = rowmin.min()
        if not np.isfinite(m):
            return seq
        thr = _tie(m)
        n = int(np.argmax(rowmin <= thr))
        j = int(np.argmax(feas[n] & (s[n] <= thr)))
        seq.append((n, j))
        free[j] -= D[n]
        tot[n] += 1
        wants[n] = tot[n] < wanted[n]
        # column j: its free (= residual) capacity fell
        old = np.where(feas[:, j], s[:, j], np.inf)
        fits, dom[:, j] = _column(D, wants, free[j])
        s[:, j] = rnd(tot / phi * dom[:, j])
        feas[:, j] = fits
        new = np.where(fits, s[:, j], np.inf)
        cnt[(old == rowmin) & (new != rowmin)] -= 1
        # row n: its executor count rose
        s[n] = rnd(tot[n] / phi[n] * dom[n])
        feas[n] &= wants[n]
        zero = np.flatnonzero(cnt <= 0)
        if len(zero):
            rescan(zero)
        rescan_row(n)


def _rrr(criterion, D, rnd, *, tot, wanted, phi, free, ctot, rng) -> list:
    """Randomized round-robin over columns; the visited column's feasible
    row with the least score wins.  A per-column count of feasible rows
    keeps each step O(rows + columns)."""
    W, J = len(D), len(free)
    pairwise = criterion == "rpsdsf"
    wants = tot < wanted
    feas = _fits(D, free) & wants[:, None]
    okc = feas.sum(axis=0)
    if pairwise:
        dom = _dominant(D, free)
        s = rnd((tot / phi)[:, None] * dom)
    else:
        unit = (D / np.maximum(np.asarray(ctot, np.float64), 1e-30)).max(1)
        s = rnd(tot * unit / phi)
    perm, pos = rng.permutation(J), 0
    seq = []
    while okc.any():
        ahead = okc[perm[pos:]] > 0
        if ahead.any():
            k = pos + int(np.argmax(ahead))
        else:
            perm, pos = rng.permutation(J), 0
            k = int(np.argmax(okc[perm] > 0))
        j = int(perm[k])
        col = np.where(feas[:, j], s[:, j] if pairwise else s, np.inf)
        n = int(np.argmax(col <= _tie(col.min())))
        seq.append((n, j))
        pos = k + 1
        if pos == J:
            perm, pos = rng.permutation(J), 0
        free[j] -= D[n]
        tot[n] += 1
        if not tot[n] < wanted[n]:
            wants[n] = False
            okc -= feas[n]
            feas[n] = False
        feas[:, j], dom_j = _column(D, wants, free[j])
        okc[j] = feas[:, j].sum()
        if pairwise:
            dom[:, j] = dom_j
            s[:, j] = rnd(tot / phi * dom[:, j])
            s[n] = rnd(tot[n] / phi[n] * dom[n])
        else:
            s[n] = rnd(tot[n] * unit[n] / phi[n])
    return seq


def bfloat16_round(x):
    """Scores held in bfloat16 (the control)."""
    import ml_dtypes

    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)
