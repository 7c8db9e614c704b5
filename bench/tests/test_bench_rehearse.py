"""Whole runs of each cell at rehearsal size on the CPU: the result line,
the control, and faults planted under the timed path that ``correct`` must
catch."""
import json
import os
import subprocess
import sys

import pytest

from bench import drive, run, spec
from repro.core import engine_jax
from repro.launch import alloc_serve

CELLS = ("borg2011-rpsdsf.fill", "alibaba2018-drf-rrr.churn")


def _run(cell, seed=11, seconds=1.0, trace=0, **kw):
    args = run.parse(["--workload", cell, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace), "--rehearse"])
    return run.run_cell(args, **kw)


def _well_formed(res, cell, names):
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0
    assert set(res["metrics"]) == names
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    json.dumps(res)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_end_to_end_metrics(cell):
    res = _run(cell)
    assert res["correct"] is True and res["failed"] == 0
    names = {m["name"] for m in spec.load_cell(cell).end_to_end}
    _well_formed(res, cell, names)
    assert all(c["value"] == 0 for c in res["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_prints_host_span_metrics(cell):
    res = _run(cell, trace=1)
    assert res["correct"] is True
    # the CPU has no device plane: the device metrics stay silent
    names = {m["name"] for m in spec.load_cell(cell).per_layer
             if m["source"] == "program_span"}
    _well_formed(res, cell, names)
    assert "busy_s" not in res["device"]


@pytest.mark.parametrize("cell,seed", [("borg2011-rpsdsf.fill", 6)])
def test_the_control_is_not_correct(cell, seed):
    # a churn rehearsal's epochs hold an arrival or two, too few for
    # bfloat16 to reorder: test_bench_reference checks a churn-size epoch
    res = _run(cell, seed=seed, control=True)
    assert res["correct"] is True
    assert res["control"]["checks"]["epochs_mismatched"]["value"] > 0


def test_epoch_shapes_count_the_rows_the_epoch_ran_over():
    cell = spec.load_cell("borg2011-rpsdsf.fill", rehearse=True)
    service, log, plan = drive.set_up(cell, 5, 0.0)
    standing = len(service.alloc.frameworks)
    out = drive.run_rounds(cell, service, log, plan, 5, 0.0)
    n_fw, n_machines, grants = out.epoch_shapes[0]
    assert n_fw == standing + cell.traffic["batch"]
    assert n_machines == len(plan["agents"]) and grants == out.grants > 0


def test_fill_warm_up_grows_the_state_to_the_round_without_an_epoch():
    cell = spec.load_cell("borg2011-rpsdsf.fill", rehearse=True)
    service, log, plan = drive.set_up(cell, 5, 0.0)
    standing = len(service.alloc.frameworks)
    assert drive.warm_up(cell, service, log, plan, 5) == 0
    assert len(service.alloc.frameworks) == standing
    rows = service.alloc.state.X.shape[0]
    assert rows >= standing + cell.traffic["batch"]
    drive.run_rounds(cell, service, log, plan, 5, 0.0)
    assert service.alloc.state.X.shape[0] == rows      # no growth in a round


def _drop_every_other_request(orig):
    def drain(self):
        self._queue[:] = self._queue[1::2]
        return orig(self)
    return drain


def _alter_last_grant(orig):
    def result(self):
        seq = orig(self)
        if seq:
            n, j = seq[-1]
            seq[-1] = (n, (j + 1) % 3)
        return seq
    return result


FAULTS = {
    "state_unchanged": (alloc_serve.AllocatorService, "complete",
                        lambda orig: lambda self, fid: None),
    "half_the_batch": (alloc_serve.AllocatorService, "drain_epoch",
                       _drop_every_other_request),
    "answer_altered": (engine_jax.EpochHandle, "result", _alter_last_grant),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    owner, attr, make = FAULTS[fault]
    monkeypatch.setattr(owner, attr, make(getattr(owner, attr)))
    res = _run(cell, seconds=1.5)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "bench", "run.py"),
         "--workload", CELLS[1], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "TPU" in p.stderr
