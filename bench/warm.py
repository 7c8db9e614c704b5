"""Compile (or load from the persistent cache) every epoch program the
window can run, and no other, before the window opens.

The fused epoch's program is keyed by the padded (frameworks, machines)
shape, by ``max_steps`` and, under RRR, by the height of the permutation
stack; the last two follow from the epoch's grant bound.  For each shape
and each grant bound at which one of those changes, the program itself is
asked to dispatch an epoch over synthetic inputs on which nothing is
feasible: it picks and compiles its own program, and the loop exits at
once.
"""
from __future__ import annotations

import math

import numpy as np


def _pow2(n: int, lo: int = 8) -> int:
    return max(lo, 1 << max(0, int(n) - 1).bit_length())


def bounds_up_to(bmax: int, J: int) -> list:
    """Grant bounds at every point where the step bucket or the RRR stack
    height may change, up to ``bmax``."""
    pts = {1, bmax}
    k = 1
    while k < bmax:
        pts.add(k + 1)
        k *= 2
    m = J
    while m < bmax:
        pts.add(m + 1)
        m += J
    return sorted(p for p in pts if 1 <= p <= bmax)


def frameworks_range(mix: dict, n_steady: int) -> list:
    """Padded framework buckets the open loop can reach: the steady count,
    Poisson spread and a second of arrivals either side."""
    spread = 3 * math.sqrt(n_steady) + float(mix["rate_rps"])
    lo, hi = max(1, int(n_steady - spread)), int(n_steady + spread) + 1
    return sorted({_pow2(n) for n in (lo, hi, n_steady)})


def round_bound(batch, free) -> int:
    """The grant bound of a closed-loop round: its batch on a cluster with
    ``free`` (J, R) left, as the program reckons it."""
    from repro.core import engine_jax

    D = np.asarray([r.demand for r in batch])
    return engine_jax.grant_bound(
        D, free, np.zeros(len(batch)),
        np.asarray([r.n_executors for r in batch], float))


def warm(cell, service, plan, seed) -> int:
    """Dispatch the empty epochs of the cell; returns how many.  A
    closed-loop round always has the same shape and bound (every round
    offers the same multiset on the same standing load); an open loop
    reaches a range of both."""
    from bench import traffic
    from repro.core import engine_jax

    cfg, mix = cell.config, cell.traffic
    st = service.alloc.state
    J, R = st.n_agents, st.R
    n_now = len(service.alloc.frameworks)
    if mix["loop"] == "rounds":
        batch = traffic.batch(mix, cfg, seed, 0)
        free = np.asarray(list(service.alloc.free.values()), float)
        frameworks = [_pow2(n_now + len(batch))]
        bounds = [round_bound(batch, free)]
    else:
        total = sum(req.n_executors for _, req, _ in plan["arrivals"])
        frameworks = frameworks_range(mix, n_now)
        bounds = bounds_up_to(total, J)
    for N in frameworks:
        X = np.zeros((N, J))
        D = np.ones((N, R))
        free = np.full((J, R), 1e9)
        for b in bounds:
            wanted = np.zeros(N)
            wanted[0] = b
            engine_jax.run_epoch_async(
                cfg["criterion"], cfg["server_policy"], X=X, D=D,
                C=free, FREE=free, phi=np.ones(N),
                allowed=np.zeros((N, J), bool), wanted=wanted,
                true_demands=D, rng=np.random.default_rng(0)).result()
    return len(frameworks) * len(bounds)
