"""The plain reference against the program's host epoch, on small
instances, and its controls."""
import numpy as np
import pytest

from bench import reference, spec, traffic
from repro.core.online import OnlineAllocator


def _instance(seed, n_fw=30, n_agents=40):
    rng = np.random.default_rng(seed)
    caps = rng.choice([64.0, 128.0, 256.0], size=(n_agents, 2))
    free = caps * rng.uniform(0.0, 0.6, size=(n_agents, 1))
    free = np.floor(free)                       # integer, exact
    dem = np.ldexp(1.0, rng.integers(1, 5, size=(n_fw, 2)))
    held = rng.integers(0, 3, size=n_fw).astype(float)
    wanted = held + rng.integers(1, 20, size=n_fw)
    return caps, free, dem, held, wanted


@pytest.mark.parametrize("seed", range(6))
def test_pooled_rpsdsf_matches_the_host_epoch(seed):
    caps, free, dem, held, wanted = _instance(seed)
    held[:] = 0
    alloc = OnlineAllocator(2, criterion="rpsdsf", server_policy="pooled",
                            seed=seed)
    _load(alloc, caps, free, dem, wanted)
    got = [(g.fid, g.agent) for g in alloc.allocate_batched(use_kernel=False)]
    seq = reference.epoch("rpsdsf", "pooled", D=dem, tot=held,
                          wanted=wanted, phi=np.ones(len(dem)), free=free)
    assert got and got == [(f"f{n:03d}", f"m{j:03d}") for n, j in seq]


@pytest.mark.parametrize("seed", range(6))
def test_rrr_drf_matches_the_host_epoch(seed):
    caps, free, dem, held, wanted = _instance(seed)
    held[:] = 0
    alloc = OnlineAllocator(2, criterion="drf", server_policy="rrr",
                            seed=seed)
    _load(alloc, caps, free, dem, wanted)
    state = alloc.rng.bit_generator.state
    got = [(g.fid, g.agent) for g in alloc.allocate_batched(use_kernel=False)]
    rng = np.random.Generator(np.random.PCG64())
    rng.bit_generator.state = state
    seq = reference.epoch("drf", "rrr", D=dem, tot=held, wanted=wanted,
                          phi=np.ones(len(dem)), free=free,
                          ctot=caps.sum(axis=0), rng=rng)
    assert got and got == [(f"f{n:03d}", f"m{j:03d}") for n, j in seq]


def _load(alloc, caps, free, dem, wanted):
    """Machines at ``caps`` occupied down to ``free`` by one holder each
    (holders sort after the frameworks and want nothing more)."""
    for j in range(len(caps)):
        alloc.add_agent(f"m{j:03d}", caps[j])
    for j in range(len(caps)):
        used = caps[j] - free[j]
        if used.any():
            alloc.register(f"x{j:03d}", demand=used, wanted_tasks=1)
            alloc.force_place(f"x{j:03d}", f"m{j:03d}")
    for n in range(len(dem)):
        alloc.register(f"f{n:03d}", demand=dem[n],
                       wanted_tasks=int(wanted[n]))


def test_the_bfloat16_control_departs_from_the_reference():
    """On the fill mix over a fiftieth of the Borg cell, scores held in
    bfloat16 reorder grants on every seed tried."""
    cell = spec.load_cell("borg2011-rpsdsf.fill")
    cfg, mix = dict(cell.config), dict(cell.traffic, batch=100)
    cfg["machines"] = [dict(m, count=max(1, m["count"] // 50))
                       for m in cfg["machines"]]
    for seed in (1, 2, 3):
        agents = traffic.roster(cfg, seed)
        fws, places = traffic.standing(mix, cfg, agents, seed)
        index = {a: j for j, (a, _) in enumerate(agents)}
        free = np.asarray([c for _, c in agents], float)
        dem = {f: np.asarray(d) for f, d, _, _ in fws}
        for f, a, n in places:
            free[index[a]] -= n * dem[f]
        batch = traffic.batch(mix, cfg, seed, 0)
        kw = dict(D=np.asarray([r.demand for r in batch]),
                  tot=np.zeros(len(batch)),
                  wanted=np.asarray([r.n_executors for r in batch], float),
                  phi=np.ones(len(batch)), free=free)
        exact = reference.epoch("rpsdsf", "pooled", **kw)
        rough = reference.epoch("rpsdsf", "pooled",
                                score_round=reference.bfloat16_round, **kw)
        assert exact != rough, seed


def test_the_bfloat16_control_departs_on_a_churn_epoch():
    """A second of the churn cell's arrivals in one epoch, over a fiftieth
    of the Alibaba cell: DRF scores held in bfloat16 reorder grants on
    every seed tried."""
    cell = spec.load_cell("alibaba2018-drf-rrr.churn")
    cfg = dict(cell.config)
    cfg["machines"] = [dict(m, count=max(1, m["count"] // 50))
                       for m in cfg["machines"]]
    for seed in (1, 2, 3):
        free = np.asarray([c for _, c in traffic.roster(cfg, seed)], float)
        reqs = [r for _, r, _ in traffic.arrivals(cell.traffic, cfg, 1.0,
                                                  seed)]
        kw = dict(D=np.asarray([r.demand for r in reqs]),
                  tot=np.zeros(len(reqs)),
                  wanted=np.asarray([r.n_executors for r in reqs], float),
                  phi=np.ones(len(reqs)), free=free, ctot=free.sum(axis=0))
        exact = reference.epoch("drf", "rrr", rng=np.random.default_rng(seed),
                                **kw)
        rough = reference.epoch("drf", "rrr", rng=np.random.default_rng(seed),
                                score_round=reference.bfloat16_round, **kw)
        assert exact != rough, seed


def test_no_reference_for_uncovered_configurations():
    with pytest.raises(ValueError):
        reference.epoch("tsf", "pooled", D=np.ones((1, 2)), tot=[0],
                        wanted=[1], phi=[1], free=np.ones((1, 2)))
