"""The one traffic generator: rosters, request shapes, rounds and arrivals.

Every number comes from the configuration file, the mix file (with the
cell's own parameters over it) and ``--seed``.  The multiset of request
sizes, demands, weights, gaps and hold times is drawn once from the mix's
``shape_seed``; ``--seed`` only permutes it and places it.  So every seed
offers the same work, in another order and on another layout.

Per-executor demands come from the configuration's ``executor_demand``
table, in its capacity units.  Each value has at most 8 significant bits,
so it is exact in bfloat16 and float32 (the chip's bf16 products stay
exact), while scores built from several of them are not.  Framework
weights come from its optional ``weights`` table, ``[[phi, p], ...]`` in
the same form and under the same rule, drawn on streams of their own;
without one every weight is 1 and nothing is drawn.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    fid: str
    demand: tuple          # per-executor demand, one entry per resource
    n_executors: int
    phi: float = 1.0       # the framework's weight


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def roster(config: dict, seed: int) -> list:
    """``[(agent name, capacity tuple), ...]``: the configuration's machines,
    their order across names permuted by ``seed``."""
    caps = [tuple(float(c) for c in m["capacity"])
            for m in config["machines"] for _ in range(int(m["count"]))]
    order = _rng(seed, 1).permutation(len(caps))
    width = len(str(len(caps)))
    return [(f"m{j:0{width}d}", caps[k]) for j, k in enumerate(order)]


def largest(config: dict) -> np.ndarray:
    return np.max([m["capacity"] for m in config["machines"]], axis=0)


def _bf16_exact(values: np.ndarray) -> bool:
    m = np.frexp(values)[0]
    return bool((np.round(m * 256) == m * 256).all())


def _draw(table: list, scale: float, n: int, rng, what: str) -> np.ndarray:
    """``n`` values of a ``[[value, p], ...]`` table, each times ``scale``."""
    rows = np.asarray(table, float)
    values = rows[:, 0] * scale
    if not _bf16_exact(values):
        raise ValueError(f"{what} need more than 8 significant bits: "
                         f"{values.tolist()}")
    return rng.choice(values, size=n, p=rows[:, 1] / rows[:, 1].sum())


def _demands(table: dict, resources: list, scale, n: int, rng) -> np.ndarray:
    """(n, R) demands: per resource, a value of the table's ``[[value, p],
    ...]`` times that resource's ``scale``."""
    out = np.empty((n, len(resources)))
    for r, res in enumerate(resources):
        out[:, r] = _draw(table[res], scale[r], n, rng, f"{res} demands")
    return out


def _weights(mix: dict, config: dict, n: int, stream: int) -> np.ndarray:
    """``n`` framework weights from the configuration's ``weights`` table,
    on a stream of their own; all 1, with no draw, where it has none."""
    if "weights" not in config:
        return np.ones(n)
    phi = _draw(config["weights"], 1.0, n, _rng(mix["shape_seed"], 9, stream),
                "weights")
    if not (phi > 0).all():
        raise ValueError(f"weights must be positive: {sorted(set(phi))}")
    return phi


def _executors(law: dict, n: int, rng) -> np.ndarray:
    """Heavy-tailed executor counts: P(k) proportional to k**-exponent on
    1..max."""
    k = np.arange(1, int(law["max"]) + 1)
    p = k ** -float(law["exponent"])
    return rng.choice(k, size=n, p=p / p.sum())


def shapes(mix: dict, config: dict, n: int, stream: int):
    """The fixed multiset of ``n`` request shapes (executors, demands,
    weights) of this mix: drawn from ``shape_seed`` alone, never from
    ``--seed``."""
    rng = _rng(mix["shape_seed"], stream)
    return (_executors(mix["executors"], n, rng),
            _demands(config["executor_demand"], config["resources"],
                     np.ones(len(config["resources"])), n, rng),
            _weights(mix, config, n, stream))


# -- closed loop: a standing load, then batches in rounds -------------------

def standing(mix: dict, config: dict, agents: list, seed: int):
    """The standing load: ``[(fid, demand, wanted, phi)], [(fid, agent,
    n)]``.

    Each machine holds executors of one long-running framework up to a
    share of its capacity drawn from ``occupancy`` ([low, high]); their
    demands are fractions of the largest machine."""
    st = mix["standing"]
    k = int(st["frameworks"])
    dem = _demands(st["demand_fraction"], config["resources"],
                   largest(config), k, _rng(mix["shape_seed"], 7))
    phi = _weights(mix, config, k, 7)
    rng = _rng(seed, 2)
    owner = rng.integers(0, k, size=len(agents))
    share = rng.uniform(*st["occupancy"], size=len(agents))
    caps = np.asarray([c for _, c in agents])
    count = np.floor((share[:, None] * caps / dem[owner]).min(axis=1))
    fids = [f"s{i:04d}" for i in range(k)]
    wanted = np.bincount(owner, weights=count, minlength=k)
    frameworks = [(fids[i], tuple(dem[i]), int(wanted[i]), float(phi[i]))
                  for i in range(k) if wanted[i] > 0]
    places = [(fids[owner[j]], agents[j][0], int(count[j]))
              for j in range(len(agents)) if count[j] > 0]
    return frameworks, places


def batch(mix: dict, config: dict, seed: int, rnd: int) -> list:
    """Round ``rnd``'s batch: the mix's fixed multiset, permuted by seed."""
    n = int(mix["batch"])
    execs, dem, phi = shapes(mix, config, n, stream=3)
    order = _rng(seed, 3, rnd % 100000).permutation(n)
    return [Request(f"r{rnd % 100000:05d}f{i:05d}", tuple(dem[k]),
                    int(execs[k]), float(phi[k]))
            for i, k in enumerate(order)]


# -- open loop: a steady state, then Poisson arrivals -----------------------

def hold_mean(mix: dict) -> float:
    """Little's law: steady frameworks / arrival rate."""
    return float(mix["frameworks_steady"]) / float(mix["rate_rps"])


def _lognormal(mean: float, sigma: float, n: int, rng) -> np.ndarray:
    return rng.lognormal(np.log(mean) - sigma ** 2 / 2, sigma, size=n)


def steady(mix: dict, config: dict, agents: list, seed: int):
    """The steady state at the window's start: ``[(Request, residual hold
    s)]`` and ``[(fid, agent, n)]`` placements.  Residual holds are those
    of a length-biased draw, as a renewal process seen at a random time.
    Each executor sits on a machine drawn from ``seed`` that has room."""
    n = int(mix["frameworks_steady"])
    execs, dem, phi = shapes(mix, config, n, stream=4)
    sigma = float(mix["hold"]["sigma"])
    base = _rng(mix["shape_seed"], 5)
    # length-biased lognormal: the same sigma, the mean times exp(sigma^2)
    biased = _lognormal(hold_mean(mix) * np.exp(sigma ** 2), sigma, n, base)
    residual = biased * base.uniform(size=n)
    rng = _rng(seed, 4)
    order = rng.permutation(n)
    free = np.asarray([c for _, c in agents], float)
    out, places = [], []
    for i, k in enumerate(order):
        req = Request(f"b{i:07d}", tuple(dem[k]), int(execs[k]),
                      float(phi[k]))
        out.append((req, float(residual[k])))
        counts: dict = {}
        for _ in range(req.n_executors):
            for _try in range(64):
                j = int(rng.integers(len(agents)))
                if (free[j] >= dem[k]).all():
                    break
            else:
                raise ValueError("steady state does not fit the cluster")
            free[j] -= dem[k]
            counts[j] = counts.get(j, 0) + 1
        places.extend((req.fid, agents[j][0], c)
                      for j, c in sorted(counts.items()))
    return out, places


def arrivals(mix: dict, config: dict, seconds: float, seed: int):
    """``[(due offset s, Request, hold s)]`` for a window of ``seconds``:
    ``rate_rps * seconds`` requests whose gaps are a fixed multiset of
    exponential gaps scaled to span the window, permuted by ``seed``."""
    n = max(1, int(round(float(mix["rate_rps"]) * seconds)))
    execs, dem, phi = shapes(mix, config, n, stream=6)
    base = _rng(mix["shape_seed"], 8)
    gaps = base.exponential(size=n)
    holds = _lognormal(hold_mean(mix), float(mix["hold"]["sigma"]), n, base)
    rng = _rng(seed, 5)
    gaps = gaps[rng.permutation(n)]
    due = np.cumsum(gaps) / gaps.sum() * seconds * n / (n + 1)
    order = rng.permutation(n)
    return [(float(due[i]),
             Request(f"a{i:07d}", tuple(dem[order[i]]), int(execs[order[i]]),
                     float(phi[order[i]])),
             float(holds[order[i]])) for i in range(n)]
