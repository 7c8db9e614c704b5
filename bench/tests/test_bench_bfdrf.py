"""The BF-DRF configuration: its cell runs at rehearsal size with
``correct`` true, on the device path; the plain BF-DRF reference follows the
paper's BF-DRF filler and its control breaks it."""

import numpy as np
import pytest

from bench import drive, run, spec, traffic
from bench.reference import bfloat16_round
from repro.core.filling import PAPER_SCHEDULERS, progressive_fill
from repro.core.instance import make_instance, spark_cluster_heterogeneous

FILL = "borg2011-bfdrf.fill"


def _run(cell, seed=2718281828, trace=0, **kw):
    args = run.parse(["--workload", cell, "--seed", str(seed), "--seconds",
                      "1", "--trace", str(trace), "--rehearse"])
    return run.run_cell(args, **kw)


@pytest.mark.parametrize("trace", (0, 1))
def test_the_new_cell_rehearses_correct(trace):
    res = _run(FILL, trace=trace)
    assert res["correct"] is True and res["failed"] == 0
    assert all(c["value"] == 0 for c in res["checks"].values())
    loaded = spec.load_cell(FILL)
    names = ({m["name"] for m in loaded.end_to_end} if not trace else
             {m["name"] for m in loaded.per_layer
              if m["source"] == "program_span"})
    assert set(res["metrics"]) == names
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_every_fill_epoch_runs_on_the_device():
    cell = spec.load_cell(FILL, rehearse=True)
    service, log, plan = drive.set_up(cell, 5, 0.5)
    out = drive.run_rounds(cell, service, log, plan, 5, 0.5)
    c = out.counters
    assert out.grants > 0 and c["dispatches"] >= out.epochs > 0
    assert c["cache_hits"] == 0 and not c["faults_nonzero"]


def _reference(config=None):
    return spec.reference(config or spec.load_cell(FILL).config)


@pytest.mark.parametrize("seed", range(4))
def test_the_reference_is_the_bf_drf_filler(seed):
    """The plain reference follows ``core.filling``'s BF-DRF on the paper's
    cluster and on seeded integer clusters (wanting without end)."""
    if seed == 0:
        inst = spark_cluster_heterogeneous()
    else:
        rng = np.random.default_rng(seed)
        inst = make_instance(
            demands=rng.integers(1, 9, size=(5, 2)).astype(float),
            capacities=rng.integers(8, 64, size=(12, 2)).astype(float),
            weights=rng.choice([0.5, 1.0, 2.0], size=5))
    want = progressive_fill(inst, PAPER_SCHEDULERS["BF-DRF"]).order
    W, C = inst.n_frameworks, inst.capacities
    got = _reference()(spec.load_cell(FILL).config, D=inst.demands,
                       tot=np.zeros(W), wanted=np.full(W, 1e9),
                       phi=inst.weights, free=C.copy(), ctot=C.sum(axis=0))
    assert len(got) > 10 and got == want


def test_the_bfloat16_control_departs_from_the_reference():
    """On the fill mix over a fiftieth of the Borg cell, scores held in
    bfloat16 reorder grants on every seed tried."""
    cell = spec.load_cell(FILL)
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
                  phi=np.ones(len(batch)), free=free,
                  ctot=free.sum(axis=0))
        exact = cell.reference(cfg, **kw)
        rough = cell.reference(cfg, score_round=bfloat16_round, **kw)
        assert exact != rough, seed


def test_the_control_is_not_correct():
    res = _run(FILL, seed=6, control=True)
    assert res["correct"] is True
    assert res["control"]["checks"]["epochs_mismatched"]["value"] > 0
