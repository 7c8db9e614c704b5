"""A configuration brings its own reference, weights and service settings
as files: a new criterion/policy pair loads and replays with no file of the
harness edited, and the two cells' generated work is the parent's."""
import ast
import glob
import hashlib
import json
import os
import shutil

import pytest

from bench import drive, ledger, reference, spec, traffic
from repro.core.online import OnlineAllocator

CELLS = ("borg2011-rpsdsf.fill", "alibaba2018-drf-rrr.churn")


def _write(root, rel, obj):
    path = root / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(obj if isinstance(obj, str) else json.dumps(obj))


def _bench_files():
    """Size and modification time of every file of the harness (glob skips
    dot directories such as the compilation cache)."""
    out = {}
    for path in glob.glob(os.path.join(spec.ROOT, "bench", "**", "*"),
                          recursive=True):
        if os.path.isfile(path) and "__pycache__" not in path:
            st = os.stat(path)
            out[path] = (st.st_size, st.st_mtime_ns)
    return out


def _root(tmp_path, criterion, policy, **config):
    """A benchmark of one tiny cell under ``tmp_path``."""
    _write(tmp_path, "BENCHMARK.json", {
        "configs": [{"name": "tiny", "source": "x", "why": "x",
                     "file": "bench/configs/tiny.json", "reduced": []}],
        "workloads": [{"name": "tiny.fill", "config": "tiny",
                       "traffic": "fill", "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": []})
    _write(tmp_path, "bench/configs/tiny.json", dict(
        {"resources": ["cpu", "mem"], "criterion": criterion,
         "server_policy": policy, "use_kernel": "auto",
         "epoch_cache": False}, **config))
    _write(tmp_path, "bench/traffic/fill.json", {"loop": "rounds"})
    return str(tmp_path)


#: a reference of its own for drf/bestfit: it answers with a fixed sequence
#: and keeps what it was given
STUB = '''
CALLS = []


def epoch(config, *, D, tot, wanted, phi, free, ctot, rng, score_round):
    CALLS.append((config, list(phi), len(free)))
    return {seq}
'''

#: one epoch of two frameworks (weights 2 and 1) on two machines
GRANTS = [("f0", "m0"), ("f1", "m1"), ("f0", "m1")]


def _bestfit_log():
    log = ledger.Log([("m0", (8.0, 8.0)), ("m1", (8.0, 8.0))])
    log.register("f0", (1.0, 1.0), 4, 2.0)
    log.register("f1", (1.0, 1.0), 4, 1.0)
    log.epoch(None, GRANTS, True)
    return log, {"m0": (7.0, 7.0), "m1": (6.0, 6.0)}


@pytest.mark.parametrize("seq,mismatched", [
    ("[(0, 0), (1, 1), (0, 1)]", 0),       # the sequence the log holds
    ("[(0, 0), (1, 1)]", 1),               # one grant dropped
])
def test_a_new_combination_is_added_as_files(tmp_path, seq, mismatched):
    before = _bench_files()
    root = _root(tmp_path, "drf", "bestfit",
                 weights=[[1.0, 0.5], [2.0, 0.5]],
                 service={"max_queue": 4096})
    _write(tmp_path, "bench/references/drf-bestfit.py", STUB.format(seq=seq))
    cell = spec.load_cell("tiny.fill", root=root)
    log, free = _bestfit_log()
    verdict = ledger.check(log, cell.config, cell.reference, free)
    checks = verdict["checks"]
    assert verdict["epochs_compared"] == 1
    assert checks["epochs_mismatched"]["value"] == mismatched
    assert checks["grants_oversubscribed"]["value"] == 0
    assert checks["grants_unrequested"]["value"] == 0
    (config, phi, machines), = cell.reference.__globals__["CALLS"]
    assert config["service"] == {"max_queue": 4096}
    assert phi == [2.0, 1.0] and machines == 2
    assert _bench_files() == before


def test_a_combination_with_no_reference_fails_at_load(tmp_path):
    root = _root(tmp_path, "tsf", "pooled")
    with pytest.raises(spec.SpecError, match="bench/references/tsf-pooled.py"):
        spec.load_cell("tiny.fill", root=root)


@pytest.mark.parametrize("service", [
    {"bf_metric": "cosine"},          # AllocatorService takes no such key
    {"seed": 3},                      # the harness sets it
])
def test_an_unknown_service_key_fails_at_load(tmp_path, service):
    root = _root(tmp_path, "drf", "rrr", service=service)
    with pytest.raises(spec.SpecError, match=next(iter(service))):
        spec.load_cell("tiny.fill", root=root)


def test_the_replay_uses_the_logged_weights():
    """Two frameworks of one demand on one machine with room for six
    executors: DRF at weights 2 and 1 gives the first two of every three
    grants to the heavier one, unweighted DRF alternates."""
    weighted = ["f0", "f1", "f0", "f0", "f1", "f0"]
    plain = ["f0", "f1", "f0", "f1", "f0", "f1"]
    alloc = OnlineAllocator(2, criterion="drf", server_policy="rrr", seed=3)
    alloc.add_agent("m0", (6.0, 6.0))
    alloc.register("f0", demand=(1.0, 1.0), wanted_tasks=10, phi=2.0)
    alloc.register("f1", demand=(1.0, 1.0), wanted_tasks=10, phi=1.0)
    state = alloc.rng.bit_generator.state
    got = [g.fid for g in alloc.allocate_batched(use_kernel=False)]
    assert got == weighted                 # the program's own host epoch

    config = {"criterion": "drf", "server_policy": "rrr"}
    epoch = spec.reference(config)

    def replay(fids, phi):
        log = ledger.Log([("m0", (6.0, 6.0))])
        log.register("f0", (1.0, 1.0), 10, phi[0])
        log.register("f1", (1.0, 1.0), 10, phi[1])
        log.epoch(state, [(f, "m0") for f in fids], True)
        verdict = ledger.check(log, config, epoch, {"m0": (0.0, 0.0)})
        return verdict["checks"]["epochs_mismatched"]["value"]

    assert replay(weighted, (2.0, 1.0)) == 0
    assert replay(plain, (2.0, 1.0)) == 1
    assert replay(plain, (1.0, 1.0)) == 0
    assert weighted != plain


@pytest.mark.parametrize("name,weights_matter", [
    ("borg2011-rpsdsf.fill", True),
    # a churn rehearsal's epochs hold an arrival or two: no order to change
    ("alibaba2018-drf-rrr.churn", False),
])
def test_a_weighted_configuration_is_replayed_at_its_weights(
        tmp_path, name, weights_matter):
    """The cell's files copied under ``tmp_path``, its configuration given a
    weight table and a service setting: every framework is registered, in
    the service and in the log, at the weight drawn for it, and the replay
    agrees with the program, where a replay at weight 1 would not."""
    for rel in ("BENCHMARK.json", "bench/configs", "bench/traffic",
                "bench/cells"):
        src, dst = os.path.join(spec.ROOT, rel), tmp_path / rel
        if os.path.isdir(src):
            shutil.copytree(src, dst)
        else:
            shutil.copy(src, dst)
    conf_file = tmp_path / "bench/configs" / (name.split(".")[0] + ".json")
    config = json.loads(conf_file.read_text())
    config["weights"] = [[1.0, 0.5], [2.0, 0.3], [4.0, 0.2]]
    config["service"] = {"max_queue": 100000}
    conf_file.write_text(json.dumps(config))
    cell = spec.load_cell(name, root=str(tmp_path), rehearse=True)
    assert cell.reference is reference.config_epoch

    service, log, plan = drive.set_up(cell, 17, 1.0)
    assert service.max_queue == 100000
    drive.warm_up(cell, service, log, plan, 17)
    out = drive.LOOPS[cell.traffic["loop"]](cell, service, log, plan, 17,
                                            1.0)
    assert out.grants > 0
    registered = {ev[1]: ev[4] for ev in log.events
                  if ev[0] in ("place", "register")}
    assert set(registered.values()) == {1.0, 2.0, 4.0}
    for fid, fw in service.alloc.frameworks.items():
        assert fw.phi == registered[fid]
    verdict = ledger.check(log, cell.config, cell.reference,
                           service.alloc.free)
    assert verdict["epochs_compared"] > 0
    assert all(c["value"] == 0 for c in verdict["checks"].values()), verdict
    log.events = [ev[:4] + (1.0,) + ev[5:]
                  if ev[0] in ("place", "register") else ev
                  for ev in log.events]
    unweighted = ledger.check(log, cell.config, cell.reference,
                              service.alloc.free)
    assert (unweighted["checks"]["epochs_mismatched"]["value"] > 0
            ) == weights_matter


#: sha256 of the roster, standing or steady load, placements, the first two
#: batches or ten seconds of arrivals at rehearsal size, as the parent of
#: the weights table generated them
PARENT = {
    ("borg2011-rpsdsf.fill", 7):
        "ab5d7ddea288fd93696a714df10e98b1f4a70c1438ac0ea772da5b2b18d1cda6",
    ("borg2011-rpsdsf.fill", 2 ** 31 + 12345):
        "c3c9ac06193c638e9c0ff3314c584c6c9c45cf49d77c17e50c87226f0d59b0d4",
    ("alibaba2018-drf-rrr.churn", 7):
        "3ff045da319abcda6ad3e34302a6c18d3ca41458ca17b595a52633420d29de69",
    ("alibaba2018-drf-rrr.churn", 2 ** 31 + 12345):
        "4acf6b80866505398a788713130b257c9c915e1da69da4e29ee08bec5a198a2d",
}


def _f(x):
    return tuple(float(v) for v in x)


@pytest.mark.parametrize("name,seed", sorted(PARENT))
def test_generated_requests_are_the_parents(name, seed):
    cell = spec.load_cell(name, rehearse=True)
    mix, cfg = cell.traffic, cell.config
    agents = traffic.roster(cfg, seed)
    parts = [[(a, _f(c)) for a, c in agents]]
    if mix["loop"] == "rounds":
        fws, places = traffic.standing(mix, cfg, agents, seed)
        parts += [[(f, _f(d), w) for f, d, w, _ in fws], places]
        reqs = []
        for rnd in (0, 1):
            batch = traffic.batch(mix, cfg, seed, rnd)
            parts.append([(r.fid, _f(r.demand), r.n_executors)
                          for r in batch])
            reqs += batch
        phi = [p for *_, p in fws] + [r.phi for r in reqs]
    else:
        steady, places = traffic.steady(mix, cfg, agents, seed)
        arr = traffic.arrivals(mix, cfg, 10.0, seed)
        parts += [[(r.fid, _f(r.demand), r.n_executors, h)
                   for r, h in steady], places,
                  [(d, r.fid, _f(r.demand), r.n_executors, h)
                   for d, r, h in arr]]
        phi = [r.phi for r, _ in steady] + [r.phi for _, r, _ in arr]
    assert hashlib.sha256(repr(parts).encode()).hexdigest() == PARENT[
        (name, seed)]
    assert set(phi) == {1.0}


@pytest.mark.parametrize("name", CELLS)
def test_the_cells_replay_with_the_plain_reference(name):
    assert spec.load_cell(name).reference is reference.config_epoch


def test_no_reference_imports_the_program():
    files = [os.path.join(spec.ROOT, "bench", "reference.py")] + glob.glob(
        os.path.join(spec.ROOT, "bench", "references", "*.py"))
    for path in files:
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            else:
                continue
            assert not any(n == "repro" or n.startswith("repro.")
                           for n in names), (path, names)
