"""The generator: determinism from the seed, the same work for every seed,
and demands exact in bfloat16."""
import collections

import ml_dtypes
import numpy as np
import pytest

from bench import spec, traffic

CELLS = ("borg2011-rpsdsf.fill", "alibaba2018-drf-rrr.churn")


def _cell(name):
    return spec.load_cell(name, rehearse=True)


def _shapes(reqs):
    return collections.Counter((r.demand, r.n_executors) for r in reqs)


def _requests(cell, seed):
    if cell.traffic["loop"] == "rounds":
        return traffic.batch(cell.traffic, cell.config, seed, 0)
    return [r for _, r, _ in traffic.arrivals(cell.traffic, cell.config, 5.0,
                                              seed)]


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_inputs(name):
    cell = _cell(name)
    big = 2 ** 31 + 12345
    assert _requests(cell, big) == _requests(cell, big)
    assert traffic.roster(cell.config, big) == traffic.roster(cell.config,
                                                              big)


@pytest.mark.parametrize("name", CELLS)
def test_every_seed_offers_the_same_multiset(name):
    cell = _cell(name)
    a, b = _requests(cell, 1), _requests(cell, 2)
    assert _shapes(a) == _shapes(b)
    assert a != b                       # in another order


@pytest.mark.parametrize("name", CELLS)
def test_demands_are_exact_in_bfloat16(name):
    cell = _cell(name)
    dem = np.asarray([r.demand for r in _requests(cell, 3)])
    assert (dem > 0).all()
    back = dem.astype(ml_dtypes.bfloat16).astype(np.float64)
    np.testing.assert_array_equal(back, dem)
    caps = np.asarray([c for _, c in traffic.roster(cell.config, 3)])
    back = caps.astype(np.float32).astype(np.float64)
    np.testing.assert_array_equal(back, caps)


def test_a_demand_of_more_than_8_significant_bits_is_refused():
    cell = _cell("alibaba2018-drf-rrr.churn")
    cfg = dict(cell.config, executor_demand=dict(
        cell.config["executor_demand"], mem=[[0.2, 1.0]]))
    with pytest.raises(ValueError, match="8 significant bits"):
        traffic.arrivals(cell.traffic, cfg, 1.0, 1)


def test_arrivals_span_the_window_at_the_rate():
    cell = _cell("alibaba2018-drf-rrr.churn")
    arr = traffic.arrivals(cell.traffic, cell.config, 10.0, 4)
    due = [d for d, _, _ in arr]
    assert len(arr) == round(cell.traffic["rate_rps"] * 10.0)
    assert due == sorted(due) and 0 < due[0] and due[-1] < 10.0
    assert all(h > 0 for _, _, h in arr)


def test_standing_load_fits_every_machine():
    cell = _cell("borg2011-rpsdsf.fill")
    agents = traffic.roster(cell.config, 9)
    fws, places = traffic.standing(cell.traffic, cell.config, agents, 9)
    dem = {f: np.asarray(d) for f, d, _, _ in fws}
    cap = dict(agents)
    for fid, agent, n in places:
        assert (n * dem[fid] <= np.asarray(cap[agent])).all()
    assert sum(n for _, _, n in places) == sum(w for _, _, w, _ in fws)


def test_steady_state_fits_the_cluster():
    cell = _cell("alibaba2018-drf-rrr.churn")
    agents = traffic.roster(cell.config, 5)
    steady, places = traffic.steady(cell.traffic, cell.config, agents, 5)
    assert len(steady) == cell.traffic["frameworks_steady"]
    free = {a: np.asarray(c, float) for a, c in agents}
    dem = {r.fid: np.asarray(r.demand) for r, _ in steady}
    for fid, agent, n in places:
        free[agent] -= n * dem[fid]
    assert min(f.min() for f in free.values()) >= 0
