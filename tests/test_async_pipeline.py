"""Async epoch pipeline: begin/commit double-buffering, deterministic
simulator commit points, the ``auto`` device-mesh floor, and the
donation-safe RRR replay path.

Parity contracts pinned here:

  * allocator level — ``begin_epoch``/``commit_epoch`` grant sequences are
    bit-for-bit equal to the synchronous numpy batched epoch for EVERY
    criterion x policy combo the device engine covers (and the host
    fallback serves the rest through the same begin/commit API);
  * simulator level — ``SimConfig.async_epochs=True`` reproduces the
    synchronous batched traces exactly (makespan, timeline, job durations,
    grant log) on the golden scenario grid for seeds 0-2: the commit point
    (before the next processed event, at the dispatching epoch's simulated
    time) is deterministic by construction;
  * ``auto`` mesh floor — a ``devices`` request below
    ``AUTO_MESH_MIN_CELLS`` collapses to one device, and above it fits the
    process's devices; explicit specs pass through;
  * donation-safe RRR — forced-donation grow-and-replay re-uploads from
    the host snapshot and still reproduces the numpy sequence, for every
    criterion.
"""
import warnings

import numpy as np
import pytest

from repro.core import metrics
from repro.core.instance import make_instance, spark_cluster_heterogeneous
from repro.core.online import OnlineAllocator
from repro.core.simulator import (
    HOMOGENEOUS_AGENTS,
    PI,
    WC,
    SimConfig,
    SparkMesosSim,
    run_paper_experiment,
)

CRITERIA = ("drf", "tsf", "psdsf", "rpsdsf")
DEVICE_POLICIES = ("pooled", "rrr")


def _instances():
    return {
        "heterogeneous": spark_cluster_heterogeneous(),
        "weighted": make_instance(
            demands=[[2.0, 2.0], [1.0, 3.5], [1.0, 1.0]],
            capacities=[[4.0, 14.0], [8.0, 8.0], [6.0, 11.0]],
            weights=[2.0, 1.0, 0.5],
        ),
        "constrained": make_instance(
            demands=[[2.0, 2.0], [1.0, 3.5]],
            capacities=[[4.0, 14.0], [8.0, 8.0], [6.0, 11.0]],
            weights=[1.0, 2.0],
            allowed=[[True, True, False], [True, True, True]],
        ),
    }


def _fill(inst, criterion, policy, seed, *, mode="sync", use_kernel=False,
          devices=1):
    """Drive one epoch over an Instance through the chosen path; returns
    the (fid, agent) grant order."""
    al = OnlineAllocator(inst.n_resources, criterion=criterion,
                         server_policy=policy, mode="characterized",
                         seed=seed)
    for j in range(inst.n_servers):
        al.add_agent(f"a{j:03d}", inst.capacities[j])
    for n in range(inst.n_frameworks):
        allowed = None
        if not inst.allowed[n].all():
            allowed = [f"a{j:03d}" for j in range(inst.n_servers)
                       if inst.allowed[n, j]]
        al.register(f"f{n:03d}", demand=inst.demands[n], wanted_tasks=10**6,
                    phi=inst.weights[n], allowed_agents=allowed)
    if mode == "async":
        epoch = al.begin_epoch(use_kernel=use_kernel, devices=devices)
        grants = al.commit_epoch(epoch)
    else:
        grants = al.allocate_batched(use_kernel=use_kernel, devices=devices)
    return [(g.fid, g.agent) for g in grants]


# ---------------------------------------------------------------------------
# allocator-level async parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("crit", CRITERIA)
@pytest.mark.parametrize("pol", DEVICE_POLICIES)
def test_begin_commit_matches_numpy_batched(crit, pol):
    """Async begin/commit == synchronous numpy epoch, bit-for-bit, for every
    covered combo (incl. phi != 1 and placement constraints)."""
    pytest.importorskip("jax")
    for name, inst in _instances().items():
        for seed in (0, 1, 2):
            ref = _fill(inst, crit, pol, seed, mode="sync", use_kernel=False)
            got = _fill(inst, crit, pol, seed, mode="async",
                        use_kernel="fused")
            assert ref == got, f"{name}/{seed}"


def test_begin_commit_host_fallback_matches_sync():
    """Configurations outside device coverage flow through the SAME
    begin/commit API (host fallback at begin time) with identical grants:
    best-fit after a server-specific criterion, and BF-DRF over a device
    mesh (the fused best-fit runs on one device only)."""
    inst = spark_cluster_heterogeneous()
    for crit, pol, devices in (("rpsdsf", "bestfit", 1),
                               ("drf", "bestfit", 2)):
        ref = _fill(inst, crit, pol, 0, mode="sync", use_kernel=False)
        got = _fill(inst, crit, pol, 0, mode="async", use_kernel="fused",
                    devices=devices)
        assert ref == got, f"{crit}/{pol}"


def test_run_epoch_async_is_run_epoch():
    """The engine-level handle API: dispatch-then-result equals the
    blocking wrapper (same inputs, same rng stream position)."""
    pytest.importorskip("jax")
    from repro.core import engine_jax

    inst = spark_cluster_heterogeneous()
    kw = dict(
        X=np.zeros((2, 6)), D=inst.demands, C=inst.capacities,
        FREE=inst.capacities.copy(), phi=inst.weights, allowed=inst.allowed,
        wanted=np.full(2, 10.0**6), true_demands=inst.demands,
    )
    sync = engine_jax.run_epoch("rpsdsf", "rrr",
                                rng=np.random.default_rng(3), **kw)
    handle = engine_jax.run_epoch_async("rpsdsf", "rrr",
                                        rng=np.random.default_rng(3), **kw)
    assert handle.in_flight
    seq = handle.result()
    assert not handle.in_flight
    assert seq == sync
    assert handle.result() is seq          # idempotent commit


def test_commit_epoch_guards_against_mutation_and_reuse():
    """The in-flight snapshot is invalidated by ANY state mutation, and an
    epoch cannot be committed twice."""
    pytest.importorskip("jax")
    al = OnlineAllocator(2, criterion="drf", server_policy="pooled", seed=0)
    for j in range(3):
        al.add_agent(f"a{j}", (8.0, 8.0))
    al.register("f0", demand=(1.0, 1.0), wanted_tasks=4)
    epoch = al.begin_epoch(use_kernel="fused")
    al.state.set_wanted("f0", 2)           # mutate mid-flight
    with pytest.raises(RuntimeError, match="mutated"):
        al.commit_epoch(epoch)
    grants = al.allocate_batched(use_kernel="fused")
    assert grants
    done = al.begin_epoch(use_kernel="fused")
    al.commit_epoch(done)
    with pytest.raises(RuntimeError, match="already committed"):
        al.commit_epoch(done)


def test_overlapping_begin_epoch_refused():
    """Only one device epoch may be in flight per allocator: a second
    begin would interleave rng consumption (RRR replay top-ups draw at
    commit) and break the sequence contract."""
    pytest.importorskip("jax")
    al = OnlineAllocator(2, criterion="drf", server_policy="pooled", seed=0)
    for j in range(3):
        al.add_agent(f"a{j}", (8.0, 8.0))
    al.register("f0", demand=(1.0, 1.0), wanted_tasks=4)
    epoch = al.begin_epoch(use_kernel="fused")
    with pytest.raises(RuntimeError, match="in flight"):
        al.begin_epoch(use_kernel="fused")
    al.commit_epoch(epoch)
    al.commit_epoch(al.begin_epoch(use_kernel="fused"))   # usable again


def test_auto_kernel_keeps_rrr_on_host():
    """use_kernel='auto' must never route RRR to the fused path: the fused
    rng pre-draw would make seeded cross-epoch sequences depend on backend
    and cluster size."""
    al = OnlineAllocator(2, criterion="drf", server_policy="rrr", seed=0)
    assert al._resolve_kernel("auto", 2048, 1024, "low") is False
    al2 = OnlineAllocator(2, criterion="drf", server_policy="pooled", seed=0)
    assert al2._resolve_kernel(True, 8, 8, "low") == "fused"


def test_epoch_view_is_frozen():
    """The double-buffered upload view refuses writes."""
    al = OnlineAllocator(2, criterion="drf", seed=0)
    al.add_agent("a0", (4.0, 4.0))
    al.register("f0", demand=(1.0, 1.0), wanted_tasks=1)
    view = al.state.epoch_view()
    with pytest.raises(ValueError):
        view.FREE[0, 0] = 0.0
    # the live state is unaffected and still writable
    al.state.grant("f0", "a0", np.array([1.0, 1.0]))


# ---------------------------------------------------------------------------
# simulator-level commit-point determinism (golden scenario grid)
# ---------------------------------------------------------------------------

def _sim_fingerprint(crit, mode, agents, pol, seed, *, async_epochs,
                     use_kernel="auto"):
    cfg = SimConfig(criterion=crit, server_policy=pol, mode=mode,
                    jobs_per_queue=2, seed=seed, batched=True,
                    use_kernel=use_kernel, async_epochs=async_epochs)
    hook = metrics.GrantLogHook()
    sim = SparkMesosSim(agents, {"Pi": PI, "WordCount": WC}, cfg,
                        hooks=[hook])
    r = sim.run()
    return (r.makespan, r.timeline.shape, float(r.timeline.sum()),
            r.tasks_speculated, hook.grants,
            {g: list(map(float, v)) for g, v in r.job_durations.items()})


# the golden_sim_workloads.json scenario grid (criterion/mode/agents/policy),
# re-driven async-vs-sync: the stored golden values pin the sync per-grant
# path; THIS test pins async batched == sync batched on the same scenarios.
GOLDEN_SCENARIOS = (
    ("drf", "characterized", None, "rrr"),
    ("drf", "oblivious", None, "rrr"),
    ("psdsf", "characterized", None, "rrr"),
    ("rpsdsf", "characterized", None, "bestfit"),
    ("tsf", "characterized", HOMOGENEOUS_AGENTS, "pooled"),
)


@pytest.mark.parametrize("crit,mode,agents,pol", GOLDEN_SCENARIOS,
                         ids=lambda v: v if isinstance(v, str) else "")
def test_commit_point_golden_async_equals_sync(crit, mode, agents, pol):
    """Seeds 0-2 of every golden scenario: the async pipeline's commit
    points reproduce the synchronous batched trace bit-for-bit (fused,
    host-fallback and oblivious configurations alike)."""
    from repro.core.simulator import HETEROGENEOUS_AGENTS

    ag = agents or HETEROGENEOUS_AGENTS
    for seed in (0, 1, 2):
        sync = _sim_fingerprint(crit, mode, ag, pol, seed,
                                async_epochs=False, use_kernel="fused")
        asyn = _sim_fingerprint(crit, mode, ag, pol, seed,
                                async_epochs=True, use_kernel="fused")
        assert sync == asyn, f"{crit}/{mode}/{pol}/seed{seed}"


def test_async_requires_batched():
    with pytest.raises(ValueError, match="batched"):
        SparkMesosSim([("a0", (4.0, 4.0))], {"Pi": PI, "WordCount": WC},
                      SimConfig(async_epochs=True, batched=False))


def test_async_auto_kernel_runs_to_completion():
    """async + use_kernel='auto' (the small-cluster host-fallback route)
    completes and matches the sync run."""
    r_sync = run_paper_experiment("psdsf", "characterized", jobs_per_queue=1,
                                  seed=0, batched=True, server_policy="pooled")
    r_async = run_paper_experiment("psdsf", "characterized", jobs_per_queue=1,
                                   seed=0, batched=True,
                                   server_policy="pooled", async_epochs=True)
    assert r_sync.makespan == r_async.makespan
    np.testing.assert_array_equal(r_sync.timeline, r_async.timeline)


# ---------------------------------------------------------------------------
# the auto device-mesh floor
# ---------------------------------------------------------------------------

def test_auto_partition_floors_clamp_small_epochs():
    """use_kernel='auto' collapses a devices request below the measured
    floor to one device, and fits a mesh above it to the process's
    devices; explicit specs pass through untouched."""
    jax = pytest.importorskip("jax")
    from repro.core.engine import AUTO_MESH_MIN_CELLS

    al = OnlineAllocator(2, criterion="drf", server_policy="pooled", seed=0)
    assert al._resolve_devices("auto", 50, 25, 8) == 1
    assert al._resolve_devices("auto", 50, 25, 1) == 1
    big_n = AUTO_MESH_MIN_CELLS // 1024 + 1
    assert al._resolve_devices("auto", big_n, 1024, 8) == min(
        8, len(jax.devices()))
    assert al._resolve_devices("auto", big_n, 1024, 1) == 1
    assert al._resolve_devices("fused", 50, 25, 8) == 8
    assert al._resolve_devices(True, 50, 25, 2) == 2


# ---------------------------------------------------------------------------
# donation-safe RRR
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("crit", CRITERIA)
def test_rrr_forced_donation_replay_and_chaining_parity(crit):
    """With donation FORCED on (the non-CPU default), the RRR
    grow-and-replay path re-uploads the segment state from the host
    snapshot; grant sequences still equal the numpy engine, including
    chained overflow segments."""
    pytest.importorskip("jax")
    from repro.core import engine_jax

    inst = spark_cluster_heterogeneous()
    ref = _fill(inst, crit, "rrr", 1, mode="sync", use_kernel=False)

    def fused(**kw):
        with warnings.catch_warnings():
            # donation is a no-op on CPU and jax warns about it; the code
            # path under test (snapshot re-upload) runs regardless
            warnings.simplefilter("ignore")
            return engine_jax.run_epoch(
                crit, "rrr", X=np.zeros((2, 6)), D=inst.demands,
                C=inst.capacities, FREE=inst.capacities.copy(),
                phi=inst.weights, allowed=inst.allowed,
                wanted=np.full(2, 10.0**6), true_demands=inst.demands,
                rng=np.random.default_rng(1), _donate=True, **kw)

    order = [(f"f{n:03d}", f"a{j:03d}") for n, j in fused()]
    assert order == ref
    assert [(f"f{n:03d}", f"a{j:03d}")
            for n, j in fused(_perm_rows=2)] == ref        # grow-and-replay
    assert [(f"f{n:03d}", f"a{j:03d}")
            for n, j in fused(max_steps_cap=16, _perm_rows=2)] == ref
