"""Async epoch pipeline: begin/commit double-buffering, deterministic
simulator commit points, the sharded device-epoch select, and the
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
  * sharded select — ``shards=K`` epochs equal the unsharded loop, and a
    new shard count costs AT MOST one retrace per shape bucket;
  * donation-safe RRR — forced-donation grow-and-replay re-uploads from
    the host snapshot and still reproduces the numpy sequence.
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
          shards=1):
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
        epoch = al.begin_epoch(use_kernel=use_kernel, shards=shards)
        grants = al.commit_epoch(epoch)
    else:
        grants = al.allocate_batched(use_kernel=use_kernel, shards=shards)
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
    best-fit after a server-specific criterion, and BF-DRF over shards
    (the fused best-fit runs on one shard only)."""
    inst = spark_cluster_heterogeneous()
    for crit, pol, shards in (("rpsdsf", "bestfit", 1),
                              ("drf", "bestfit", 2)):
        ref = _fill(inst, crit, pol, 0, mode="sync", use_kernel=False)
        got = _fill(inst, crit, pol, 0, mode="async", use_kernel="fused",
                    shards=shards)
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
# sharded device-epoch select
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("crit", CRITERIA)
@pytest.mark.parametrize("pol", DEVICE_POLICIES)
def test_sharded_epoch_matches_unsharded(crit, pol):
    """shards=K partitions the in-loop selects; grant sequences equal the
    unsharded loop AND the numpy engine on every instance."""
    pytest.importorskip("jax")
    for name, inst in _instances().items():
        ref = _fill(inst, crit, pol, 0, mode="sync", use_kernel=False)
        for shards in (2, 4):
            got = _fill(inst, crit, pol, 0, mode="sync", use_kernel="fused",
                        shards=shards)
            assert ref == got, f"{name}/shards={shards}"


def test_sharded_trace_count_regression():
    """A new shard count retraces AT MOST once per shape bucket; repeats at
    the same (bucket, shards) reuse the cached executable."""
    pytest.importorskip("jax")
    from repro.core import engine_jax

    inst = spark_cluster_heterogeneous()

    def run(shards, seed=0):
        return _fill(inst, "rpsdsf", "pooled", seed, mode="sync",
                     use_kernel="fused", shards=shards)

    run(2)                                   # enter the (bucket, 2) cache
    t0 = engine_jax.TRACE_COUNT
    run(2, seed=1)                           # same bucket + shards: cached
    assert engine_jax.TRACE_COUNT == t0
    run(4)                                   # new shard count: <= 1 trace
    assert engine_jax.TRACE_COUNT <= t0 + 1
    run(4, seed=1)
    assert engine_jax.TRACE_COUNT <= t0 + 1


@pytest.mark.parametrize("pol", DEVICE_POLICIES)
def test_sharded_wanted_exhaustion_and_limit(pol):
    """Mid-epoch ``wanted`` exhaustion + ``per_agent_limit`` under
    shards>1: the sharded loop stops at the reference count and never
    exceeds the per-agent cap."""
    pytest.importorskip("jax")
    from repro.core import engine_jax

    rng = np.random.default_rng(5)
    N, J, R = 7, 6, 2
    D = rng.uniform(0.5, 1.5, (N, R))
    C = rng.uniform(6.0, 12.0, (J, R))
    kw = dict(X=np.zeros((N, J)), D=D, C=C, FREE=C.copy(),
              phi=rng.uniform(0.5, 2.0, N),
              wanted=rng.integers(1, 3, N).astype(float),  # exhausts early
              allowed=rng.random((N, J)) > 0.2, true_demands=D,
              per_agent_limit=2)
    ref = engine_jax.run_epoch("rpsdsf", pol,
                               rng=np.random.default_rng(1), **kw)
    got = engine_jax.run_epoch("rpsdsf", pol,
                               rng=np.random.default_rng(1), shards=2, **kw)
    assert ref == got
    assert 0 < len(ref) < int(kw["wanted"].sum()) + 1
    counts = np.bincount([j for _n, j in ref])
    assert counts.max() <= 2


def test_auto_partition_floors_clamp_small_epochs():
    """use_kernel='auto' collapses shards/devices requests below the
    measured floors to the plain fused dispatch, and fits a mesh above
    them to the process's devices; explicit specs pass through untouched."""
    jax = pytest.importorskip("jax")
    from repro.core.engine import AUTO_MESH_MIN_CELLS, AUTO_SHARD_MIN_CELLS

    al = OnlineAllocator(2, criterion="drf", server_policy="pooled", seed=0)
    assert al._resolve_partition("auto", 50, 25, 8, 8) == (1, 1)
    big_n = AUTO_SHARD_MIN_CELLS // 1024 + 1
    assert al._resolve_partition("auto", big_n, 1024, 8, 1) == (8, 1)
    big_n = AUTO_MESH_MIN_CELLS // 1024 + 1
    assert al._resolve_partition("auto", big_n, 1024, 1, 8) == (
        1, min(8, len(jax.devices())))
    assert al._resolve_partition("fused", 50, 25, 8, 8) == (8, 8)
    assert al._resolve_partition(True, 50, 25, 4, 2) == (4, 2)


def test_progressive_fill_jax_sharded_parity():
    """The delegated filling_jax pooled path accepts shards and keeps its
    allocation unchanged."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.filling_jax import progressive_fill_jax

    inst = spark_cluster_heterogeneous()
    args = (jnp.asarray(inst.demands, jnp.float32),
            jnp.asarray(inst.capacities, jnp.float32),
            jnp.asarray(inst.weights, jnp.float32))
    base = progressive_fill_jax(*args, jax.random.key(0), criterion="psdsf",
                                policy="pooled", tie="low")
    sharded = progressive_fill_jax(*args, jax.random.key(0),
                                   criterion="psdsf", policy="pooled",
                                   tie="low", shards=2)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(sharded))


# ---------------------------------------------------------------------------
# donation-safe RRR
# ---------------------------------------------------------------------------

def test_rrr_forced_donation_replay_and_chaining_parity():
    """With donation FORCED on (the non-CPU default), the RRR
    grow-and-replay path re-uploads the segment state from the host
    snapshot; grant sequences still equal the numpy engine, including
    chained overflow segments."""
    pytest.importorskip("jax")
    from repro.core import engine_jax

    inst = spark_cluster_heterogeneous()
    ref = _fill(inst, "rpsdsf", "rrr", 1, mode="sync", use_kernel=False)

    def fused(**kw):
        with warnings.catch_warnings():
            # donation is a no-op on CPU and jax warns about it; the code
            # path under test (snapshot re-upload) runs regardless
            warnings.simplefilter("ignore")
            return engine_jax.run_epoch(
                "rpsdsf", "rrr", X=np.zeros((2, 6)), D=inst.demands,
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
