"""Parity suite for the unified allocator engine.

Four layers must agree on allocations:

  1. the exact numpy reference filler (`repro.core.filling`),
  2. the online allocator's batched epoch (`repro.core.engine.BatchedEpoch`
     via `OnlineAllocator.allocate_batched`),
  3. the jitted JAX engine (`repro.core.filling_jax`), and
  4. the device-resident fused epoch (`repro.core.engine_jax`, one
     lax.while_loop dispatch per epoch via `allocate_batched(use_kernel=True)`),

all dispatching into the single criterion module `repro.core.criteria`.
Layers 1 and 2 share the numpy RNG stream through the same
`repro.core.policies` objects, so their grant sequences are compared
bit-for-bit across every criterion x policy combo (including phi != 1
priorities and `allowed_agents` placement constraints).  The JAX engine
draws randomness from a different PRNG, so it is compared bit-for-bit on the
deterministic policies and distributionally under RRR (see
tests/test_filling_jax.py).

The golden test pins the *legacy per-grant* path to the pre-refactor grant
sequences (tests/golden_online_grants.json, captured before the
ClusterState refactor) for seeds 0-4 on the paper's heterogeneous cluster.
"""
import json
import os

import numpy as np
import pytest

from golden_scenario import GOLDEN_PATH, run_scenario
from repro.core.filling import FillConfig, progressive_fill
from repro.core.instance import make_instance, spark_cluster_heterogeneous
from repro.core.online import OnlineAllocator

CRITERIA = ("drf", "tsf", "psdsf", "rpsdsf")
POLICIES = ("rrr", "pooled", "bestfit")


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


def _batched_fill(inst, criterion, policy, seed, tie="low", use_kernel=False):
    """Drive the online allocator's batched epoch over an Instance; returns
    (X, grant order) with frameworks/agents named so that the allocator's
    sorted order matches the instance's index order."""
    al = OnlineAllocator(inst.n_resources, criterion=criterion,
                         server_policy=policy, mode="characterized", seed=seed)
    J = inst.n_servers
    for j in range(J):
        al.add_agent(f"a{j:03d}", inst.capacities[j])
    for n in range(inst.n_frameworks):
        allowed = None
        if not inst.allowed[n].all():
            allowed = [f"a{j:03d}" for j in range(J) if inst.allowed[n, j]]
        al.register(f"f{n:03d}", demand=inst.demands[n], wanted_tasks=10**6,
                    phi=inst.weights[n], allowed_agents=allowed)
    grants = al.allocate_batched(tie=tie, use_kernel=use_kernel)
    X = np.zeros((inst.n_frameworks, J), np.int64)
    order = []
    for g in grants:
        n, j = int(g.fid[1:]), int(g.agent[1:])
        X[n, j] += g.n_executors
        order.append((n, j))
    return X, order


@pytest.mark.parametrize("crit", CRITERIA)
@pytest.mark.parametrize("pol", POLICIES)
def test_batched_epoch_matches_reference_filler(crit, pol):
    """Same criterion code + same policy objects + same RNG stream =>
    identical grant sequences, for every instance (incl. phi != 1 and
    placement constraints) and several seeds."""
    for name, inst in _instances().items():
        for seed in (0, 1, 2):
            cfg = FillConfig(criterion=crit, server_policy=pol,
                             lookahead=False, tie="low")
            ref = progressive_fill(inst, cfg, seed=seed)
            X, order = _batched_fill(inst, crit, pol, seed, tie="low")
            np.testing.assert_array_equal(ref.x, X, err_msg=f"{name}/{seed}")
            assert ref.order == order, f"{name}/{seed}"


@pytest.mark.parametrize("crit", ["drf", "rpsdsf"])
def test_batched_epoch_matches_reference_random_ties(crit):
    """Random tie-breaking consumes the shared RNG identically."""
    inst = spark_cluster_heterogeneous()
    for seed in (0, 1, 2):
        cfg = FillConfig(criterion=crit, server_policy="rrr",
                         lookahead=False, tie="random")
        ref = progressive_fill(inst, cfg, seed=seed)
        X, order = _batched_fill(inst, crit, "rrr", seed, tie="random")
        np.testing.assert_array_equal(ref.x, X)
        assert ref.order == order


def test_jax_engine_matches_reference_weighted_constrained():
    """The JAX engine dispatches into the same criterion module; check
    bit-for-bit agreement on the deterministic policies with phi != 1 and
    placement constraints (RRR agreement is distributional — different PRNG —
    and covered in test_filling_jax.py)."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.filling_jax import progressive_fill_jax

    for name, inst in _instances().items():
        for crit, pol in [("psdsf", "pooled"), ("rpsdsf", "pooled"),
                          ("drf", "bestfit"), ("tsf", "pooled"),
                          ("drf", "pooled"), ("rpsdsf", "bestfit")]:
            xj = progressive_fill_jax(
                jnp.asarray(inst.demands, jnp.float32),
                jnp.asarray(inst.capacities, jnp.float32),
                jnp.asarray(inst.weights, jnp.float32),
                jax.random.key(0), criterion=crit, policy=pol,
                lookahead=False, tie="low",
                allowed=jnp.asarray(inst.allowed),
            )
            cfg = FillConfig(criterion=crit, server_policy=pol,
                             lookahead=False, tie="low")
            xn = progressive_fill(inst, cfg, seed=0).x
            np.testing.assert_array_equal(
                np.asarray(xj), xn, err_msg=f"{name}/{crit}/{pol}")


def test_kernel_backend_matches_numpy_batched():
    """Per-grant Pallas psdsf_score backend (characterized rPS-DSF pooled):
    the legacy boundary-crossing path, kept for benchmarking."""
    pytest.importorskip("jax")
    inst = spark_cluster_heterogeneous()
    X_np, order_np = _batched_fill(inst, "rpsdsf", "pooled", 0)
    X_k, order_k = _batched_fill(inst, "rpsdsf", "pooled", 0,
                                 use_kernel="pergrant")
    np.testing.assert_array_equal(X_np, X_k)
    assert order_np == order_k


# ---------------------------------------------------------------------------
# device-resident fused epochs (repro.core.engine_jax)
# ---------------------------------------------------------------------------

DEVICE_POLICIES = ("pooled", "rrr")


@pytest.mark.parametrize("crit", CRITERIA)
@pytest.mark.parametrize("pol", DEVICE_POLICIES)
def test_device_epoch_matches_numpy_batched(crit, pol):
    """use_kernel=True routes to the fused lax.while_loop epoch; its grant
    sequence must equal the numpy BatchedEpoch's bit-for-bit on the
    binary-exact instances (incl. phi != 1 and placement constraints).
    RRR parity holds because the fused path pre-draws its permutations from
    the same allocator rng stream the numpy RRRPolicy would consume."""
    pytest.importorskip("jax")
    for name, inst in _instances().items():
        for seed in (0, 1, 2):
            X_np, order_np = _batched_fill(inst, crit, pol, seed)
            X_d, order_d = _batched_fill(inst, crit, pol, seed,
                                         use_kernel=True)
            np.testing.assert_array_equal(X_np, X_d, err_msg=f"{name}/{seed}")
            assert order_np == order_d, f"{name}/{seed}"


def _device_alloc(crit, pol, *, wanted, limit=None, use_kernel):
    al = OnlineAllocator(2, criterion=crit, server_policy=pol,
                         mode="characterized", seed=3)
    for j in range(4):
        al.add_agent(f"a{j}", (8.0, 10.0))
    al.register("f0", demand=(2.0, 2.0), wanted_tasks=wanted, phi=2.0)
    al.register("f1", demand=(1.0, 3.5), wanted_tasks=wanted)
    al.register("f2", demand=(1.0, 1.0), wanted_tasks=3)  # exhausts mid-epoch
    grants = al.allocate_batched(per_agent_limit=limit, use_kernel=use_kernel)
    return [(g.fid, g.agent) for g in grants], al


@pytest.mark.parametrize("crit", CRITERIA)
@pytest.mark.parametrize("pol", DEVICE_POLICIES)
def test_device_epoch_limit_and_exhaustion(crit, pol):
    """per_agent_limit + a framework exhausting `wanted` mid-epoch follow
    the numpy engine exactly, and the allocator state stays consistent."""
    pytest.importorskip("jax")
    for limit in (None, 1, 2):
        seq_np, _ = _device_alloc(crit, pol, wanted=6, limit=limit,
                                  use_kernel=False)
        seq_d, al = _device_alloc(crit, pol, wanted=6, limit=limit,
                                  use_kernel=True)
        assert seq_np == seq_d, f"limit={limit}"
        assert al.frameworks["f2"].n_tasks <= 3
        for free in al.free.values():
            assert (free >= -1e-9).all()
        if limit is not None:
            per_agent = {}
            for _f, a in seq_d:
                per_agent[a] = per_agent.get(a, 0) + 1
            assert all(v <= limit for v in per_agent.values())


def test_device_epoch_one_dispatch_no_recompile():
    """The fused path runs ONE device dispatch per allocation epoch, and
    growing the cluster within the padded shape bucket (powers of two)
    reuses the cached jit executable — no retrace."""
    jax = pytest.importorskip("jax")  # noqa: F841
    from repro.core import engine_jax

    def run(n_fw, n_ag):
        al = OnlineAllocator(2, criterion="rpsdsf", server_policy="pooled",
                             mode="characterized", seed=0)
        for j in range(n_ag):
            al.add_agent(f"a{j:03d}", (8.0, 8.0))
        for n in range(n_fw):
            al.register(f"f{n:03d}", demand=(1.0 + (n % 3), 2.0),
                        wanted_tasks=4)
        return al.allocate_batched(use_kernel=True)

    run(5, 5)  # warm the jit cache for the (8, 8) bucket
    t0, d0 = engine_jax.TRACE_COUNT, engine_jax.DISPATCH_COUNT
    g1 = run(6, 6)   # same pow2 bucket (8, 8)
    g2 = run(7, 8)   # still within the bucket
    assert g1 and g2
    assert engine_jax.DISPATCH_COUNT == d0 + 2, "one dispatch per epoch"
    assert engine_jax.TRACE_COUNT == t0, \
        "same padded bucket must not retrace"


def test_grant_bound_degenerate_zero_demand_stays_finite():
    """A zero-demand framework that still wants tasks must not void the
    wanted/limit caps (the permutation stack is sized from this bound)."""
    pytest.importorskip("jax")
    from repro.core import engine_jax

    TD = np.zeros((1, 2))
    FREE = np.ones((3, 2)) * 8.0
    assert engine_jax.grant_bound(TD, FREE, np.zeros(1), np.array([5.0])) == 5
    assert engine_jax.grant_bound(TD, FREE, np.zeros(1), np.array([10.0**6]),
                                  per_agent_limit=2) == 6


def test_device_epoch_nondyadic_demands_keep_free_nonnegative():
    """Non-dyadic demands make f32 FREE arithmetic inexact on device; the
    online allocator re-validates each fused grant in f64 before applying,
    so host free capacity can never go negative."""
    pytest.importorskip("jax")
    al = OnlineAllocator(2, criterion="rpsdsf", server_policy="pooled",
                         mode="characterized", seed=0)
    for j in range(3):
        al.add_agent(f"a{j}", (30.0, 30.0))
    al.register("f0", demand=(0.3, 0.1), wanted_tasks=10**6)
    al.register("f1", demand=(0.1, 0.3), wanted_tasks=10**6)
    grants = al.allocate_batched(use_kernel=True)
    assert len(grants) > 100
    for free in al.free.values():
        assert (free >= -1e-9).all()


@pytest.mark.parametrize("crit", CRITERIA)
@pytest.mark.parametrize("pol", DEVICE_POLICIES)
def test_device_epoch_chaining_and_perm_growth_keep_parity(crit, pol):
    """An epoch that overflows max_steps_cap chains dispatches (the RRR
    cursor and, under rPS-DSF, residual capacities re-derived from the
    carried X at each segment start), and under RRR an undersized
    permutation stack grows by stream-append and replays — both must leave
    the grant sequence identical to one uncapped dispatch AND to the numpy
    engine."""
    pytest.importorskip("jax")
    from repro.core import engine_jax

    inst = spark_cluster_heterogeneous()
    _X_np, order_np = _batched_fill(inst, crit, pol, 1)

    def fused(**kw):
        return engine_jax.run_epoch(
            crit, pol, X=np.zeros((2, 6)), D=inst.demands,
            C=inst.capacities, FREE=inst.capacities.copy(), phi=inst.weights,
            allowed=inst.allowed, wanted=np.full(2, 10.0**6),
            true_demands=inst.demands, rng=np.random.default_rng(1), **kw)

    assert len(order_np) > 16                        # 16 overflows
    assert fused() == order_np
    assert fused(max_steps_cap=16) == order_np       # chained dispatches
    if pol == "rrr":
        assert fused(_perm_rows=2) == order_np       # grow-and-replay
        assert fused(max_steps_cap=16, _perm_rows=2) == order_np


#: every criterion x server policy the device epoch serves
DEVICE_COMBOS = ([(c, p) for c in CRITERIA for p in DEVICE_POLICIES]
                 + [("drf", "bestfit"), ("tsf", "bestfit")])


def _partials_case(name):
    """An epoch built against the device loop's carried select partials,
    with the ``per_agent_limit`` it runs under.  Whole-unit capacities and
    demands (best-fit needs them; the scores are then as exact in f32 as
    in float64).  The padded J is 256, two select blocks of 128 columns,
    except where the case says otherwise."""
    rng = np.random.default_rng(sum(map(ord, name)))
    N, J, limit = 6, 256, None
    C = np.full((J, 2), 16.0)
    TD = np.ones((N, 2))
    X = np.zeros((N, J))
    phi = np.ones(N)
    allowed = np.ones((N, J), bool)
    wanted = np.full(N, 8.0)
    if name == "ties-at-block-edge":
        # everything ties at the start; row 0 only past the block edge,
        # row 1 on both sides of it and before row 0's columns, so the
        # first row-major index must win over the first block
        allowed[:3] = False
        allowed[0, [130, 250]] = True
        allowed[1, [5, 127, 128]] = True
        allowed[2, [127, 128, 129]] = True
        wanted[:3] = (4.0, 4.0, 6.0)
    elif name == "unique-min-rises":
        # each row holds one executor, so scores start apart from zero;
        # one large machine per block is every row's only minimum and
        # rises with each grant it takes
        C[[60, 200]] = (64.0, 64.0)
        TD = rng.integers(1, 3, (N, 2)).astype(float)
        X[np.arange(N), np.arange(N)] = 1.0
        phi = np.array([1.0, 2.0, 1.0, 0.5, 1.0, 2.0])
        wanted = X.sum(axis=1) + 12.0
    elif name in ("exhaustion", "limit"):
        # rows run out of wants, and row 0 out of machines with room, mid-
        # epoch (and, under the limit, machines out of grants)
        C = rng.choice([4.0, 8.0, 12.0], (J, 2))
        TD = rng.integers(1, 5, (N, 2)).astype(float)
        wanted = rng.integers(1, 7, N).astype(float)
        wanted[0] = 1000.0
        allowed = rng.random((N, J)) < 0.3
        if name == "limit":
            limit = 1
    elif name == "small-J":
        # padded J (64) below one block
        J = 40
        X = np.zeros((N, J))
        C = rng.choice([8.0, 16.0], (J, 2))
        TD = rng.integers(1, 4, (N, 2)).astype(float)
        allowed = rng.random((N, J)) < 0.7
    else:
        raise ValueError(name)
    FREE = C - X.T @ TD
    return dict(X=X, D=TD, C=C, FREE=FREE, phi=phi, allowed=allowed,
                wanted=wanted, true_demands=TD), limit


PARTIALS_CASES = ("ties-at-block-edge", "unique-min-rises", "exhaustion",
                  "limit", "small-J")


def _numpy_epoch(crit, pol, inst, limit, seed):
    """The numpy ``BatchedEpoch``'s grant sequence over one epoch."""
    from repro.core.engine import BatchedEpoch

    ep = BatchedEpoch(crit, pol, rng=np.random.default_rng(seed),
                      per_agent_limit=limit, **inst)
    seq = []
    while (pick := ep.select()) is not None:
        seq.append(pick)
        ep.apply(*pick, inst["true_demands"][pick[0]])
    return seq


@pytest.mark.parametrize("case", PARTIALS_CASES)
@pytest.mark.parametrize("crit,pol", DEVICE_COMBOS)
def test_device_epoch_partials_match_numpy_batched(case, crit, pol):
    """The device loop's carried partials (per-row and per-column feasible
    counts, per-block row minima) pick exactly what the numpy engine's
    selects pick: ties on both sides of a block edge and across rows, a
    row whose only minimum is the granted column and rises there, rows and
    columns exhausted mid-epoch, a J below one block."""
    pytest.importorskip("jax")
    from repro.core import engine_jax

    inst, limit = _partials_case(case)
    want = _numpy_epoch(crit, pol, inst, limit, seed=4)
    got = engine_jax.run_epoch(crit, pol, rng=np.random.default_rng(4),
                               per_agent_limit=limit, **inst)
    assert len(want) > 10 and got == want


@pytest.mark.parametrize("crit,pol", DEVICE_COMBOS)
def test_device_epoch_partials_on_a_ragged_block(crit, pol):
    """``epoch_loop`` run directly at an unpadded J = 200, which one block
    of 128 columns does not divide: the last block is read from column 72
    and masked to its own 72 columns.  It equals the numpy engine."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core import engine_jax

    rng = np.random.default_rng(200)
    N, J = 7, 200
    assert J % engine_jax.block_width(J) != 0 < J - engine_jax.block_width(J)
    C = rng.choice([8.0, 16.0, 32.0], (J, 2))
    C[[127, 128, 199]] = 64.0                # minima at the block edges
    TD = rng.integers(1, 4, (N, 2)).astype(float)
    inst = dict(X=np.zeros((N, J)), D=TD, C=C, FREE=C.copy(),
                phi=np.array([1.0, 2.0, 1.0, 0.5, 1.0, 2.0, 1.0]),
                allowed=rng.random((N, J)) < 0.8,
                wanted=rng.integers(4, 30, N).astype(float), true_demands=TD)
    want = _numpy_epoch(crit, pol, inst, None, seed=5)
    stream = np.random.default_rng(5)
    perms = np.stack([stream.permutation(J) for _ in range(64)])
    f32 = jnp.float32
    out = engine_jax._jitted(False)(
        jnp.asarray(inst["X"], f32), jnp.asarray(TD, f32),
        jnp.asarray(TD, f32), jnp.asarray(C, f32), jnp.asarray(C, f32),
        jnp.asarray(inst["phi"], f32), jnp.asarray(inst["wanted"], f32),
        jnp.asarray(inst["allowed"]), jnp.asarray(perms, jnp.int32),
        jnp.zeros(J, jnp.int32), np.int32(0), np.int32(0), jnp.int32(J),
        np.int32(0), f32(1e-9), kind=crit, policy=pol, lookahead=False,
        use_limit=False, max_steps=256)
    ns, js, count, pidx = out[0], out[1], int(out[2]), int(out[7])
    assert pidx < len(perms) and count < 256
    got = list(zip(np.asarray(ns[:count]).tolist(),
                   np.asarray(js[:count]).tolist()))
    assert len(want) > 10 and got == want


#: the (criterion, policy) pairs the benchmark's cells serve
SERVED = (("rpsdsf", "pooled"), ("drf", "bestfit"), ("drf", "rrr"))
#: primitives that read part of a matrix or write part of it in place
_PART_PRIMS = {"dynamic_slice", "dynamic_update_slice", "gather", "scatter",
               "scatter-add"}


@pytest.mark.parametrize("kind,policy", SERVED)
def test_device_epoch_step_makes_no_whole_matrix_pass(kind, policy):
    """No step of the device loop reads the whole (N, J) state: inside the
    ``while``'s body and condition, every operation with an operand of
    N J elements only slices it or updates part of it in place, so no
    reduction, select or elementwise pass of the full matrix comes back
    into a step unnoticed.  Traced at the fill cells' (2048, 16384)."""
    jax = pytest.importorskip("jax")
    import functools

    import jax.numpy as jnp

    from repro.core import engine_jax

    N, J, R = 2048, 16384, 2
    f32, i32 = jnp.float32, jnp.int32

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    rows = engine_jax.rrr_perm_budget(16384, J) if policy == "rrr" else 1
    closed = jax.make_jaxpr(functools.partial(
        engine_jax.epoch_loop, kind=kind, policy=policy, lookahead=False,
        use_limit=True, max_steps=16384))(
        S((N, J), f32), S((N, R), f32), S((N, R), f32), S((J, R), f32),
        S((J, R), f32), S((N,), f32), S((N,), f32), S((N, J), jnp.bool_),
        S((rows, J), i32), S((J,), i32), S((), i32), S((), i32),
        S((), i32), S((), i32), S((), f32))
    (loop,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "while"]

    def passes(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name not in _PART_PRIMS and any(
                    getattr(v.aval, "size", 0) >= N * J
                    for v in eqn.invars):
                out.append(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                passes(sub, out)
        return out

    found = passes(loop.params["body_jaxpr"].jaxpr, [])
    found = passes(loop.params["cond_jaxpr"].jaxpr, found)
    assert not found, found


def test_batched_epoch_respects_per_agent_limit():
    al = OnlineAllocator(2, criterion="drf", server_policy="rrr", seed=0)
    for j in range(4):
        al.add_agent(f"a{j}", (8.0, 8.0))
    al.register("f", demand=(1.0, 1.0), wanted_tasks=100)
    grants = al.allocate(per_agent_limit=1, batched=True)
    per_agent = {}
    for g in grants:
        per_agent[g.agent] = per_agent.get(g.agent, 0) + 1
    assert per_agent and all(v == 1 for v in per_agent.values())


@pytest.mark.parametrize("crit", ["psdsf", "rpsdsf"])
@pytest.mark.parametrize("mode", ["characterized", "oblivious"])
def test_row_minima_pooled_select_equals_full_scan(monkeypatch, crit, mode):
    """The numpy epoch keeps per-row minima for the pooled select of a
    server-specific criterion; it must pick exactly what PooledPolicy's
    full N x J scan picks, on the serving roster, where hundreds of
    identical agents tie on every row (and, oblivious, where inferred
    demands move every score)."""
    from repro.core import engine
    from repro.launch import alloc_serve

    reqs = alloc_serve.make_profiles(1, 200, seed=3)[0]
    demand = {r.fid: np.asarray(r.demand) for r in reqs}
    types = alloc_serve._AGENT_TYPES

    def run():
        al = OnlineAllocator(2, criterion=crit, server_policy="pooled",
                             mode=mode, seed=0)
        al.framework_demand_oracle = demand.__getitem__
        for j in range(1000 if mode == "characterized" else 400):
            al.add_agent(f"a{j}", types[j % len(types)])
        for r in reqs:
            al.register(r.fid, wanted_tasks=r.n_executors, phi=r.phi,
                        demand=r.demand if mode == "characterized" else None)
        return [(g.fid, g.agent, int(g.n_executors))
                for g in al.allocate_batched(use_kernel=False)]

    fast = run()
    monkeypatch.setattr(engine, "_RowMinima", lambda n_rows: None)
    full = run()
    assert len(full) > 200 and fast == full


def test_batched_oblivious_epoch_consistent():
    """Oblivious batched epochs stay capacity-consistent and coarse-grained."""
    al = OnlineAllocator(2, criterion="rpsdsf", server_policy="rrr",
                         mode="oblivious", seed=0)
    al.framework_demand_oracle = lambda fid: np.array([2.0, 2.0])
    for j in range(3):
        al.add_agent(f"a{j}", (8.0, 8.0))
    al.register("pi", wanted_tasks=10)
    grants = al.allocate(batched=True)
    assert grants and grants[0].n_executors >= 1
    for j, free in al.free.items():
        assert (free >= -1e-9).all()
    assert al.frameworks["pi"].n_tasks <= 10


def test_golden_online_grant_sequences():
    """The refactored (ClusterState-backed) legacy path reproduces the
    pre-refactor grant sequences bit-for-bit: seeds 0-4, all four criteria,
    all three server policies, characterized mode, with agent churn, releases
    and weighted/constrained late arrivals (see tests/golden_scenario.py)."""
    assert os.path.exists(GOLDEN_PATH), "golden fixture missing"
    gold = json.load(open(GOLDEN_PATH))
    assert len(gold) == 60
    for key, want in gold.items():
        crit, pol, seed = key.split("/")
        got = [list(g) for g in run_scenario(crit, pol, int(seed))]
        assert got == want, f"grant sequence diverged for {key}"


def test_cluster_state_slot_reuse_and_growth():
    """Stable slots survive churn; views stay name-sorted and consistent."""
    from repro.core.cluster_state import ClusterState

    st = ClusterState(2, fw_capacity=2, agent_capacity=2)
    for i in range(5):  # force growth
        st.add_agent(f"a{i}", (4.0 + i, 8.0))
    for i in range(5):
        st.add_framework(f"f{i}", demand=(1.0, 1.0), phi=1.0 + i, wanted=3)
    st.grant("f0", "a1", np.array([1.0, 1.0]))
    st.remove_agent("a0")
    st.remove_framework("f3")
    j_new = st.add_agent("a9", (2.0, 2.0))      # reuses a0's slot
    n_new = st.add_framework("f9", demand=(0.5, 0.5),
                             allowed_agents=["a9", "a1"], wanted=1)
    assert j_new == st.agent2slot["a9"] and n_new == st.fid2slot["f9"]
    v = st.sorted_view()
    assert v.fids == ("f0", "f1", "f2", "f4", "f9")
    assert v.agents == ("a1", "a2", "a3", "a4", "a9")
    # X survived churn at the right coordinates
    assert v.X[v.fids.index("f0"), v.agents.index("a1")] == 1
    np.testing.assert_allclose(
        v.FREE[v.agents.index("a1")], np.array([5.0, 8.0]) - 1.0)
    # name-based placement constraints materialized for the sorted view
    row = v.allowed[v.fids.index("f9")]
    np.testing.assert_array_equal(
        row, [a in ("a9", "a1") for a in v.agents])
    # phi/wanted rows follow their frameworks
    assert v.phi[v.fids.index("f4")] == 5.0


# ---------------------------------------------------------------------------
# best-fit on the device: the exact cosine key (repro.core.engine_jax)
# ---------------------------------------------------------------------------

#: the Borg cell's executor demands, in 1/256 of the largest machine
#: (bench/configs/borg2011-bfdrf.json)
BORG_CPU = (2, 3, 4, 5, 6, 8, 12, 16)
BORG_MEM = (1, 2, 3, 4, 5, 6, 8, 12)


def _bfdrf_reference():
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "references", "drf-bestfit.py")
    spec = importlib.util.spec_from_file_location("drf_bestfit_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.epoch


def _bestfit_case(seed):
    """A seeded integer cluster: weights != 1, wants that run out mid-epoch,
    and collinear free vectors among the machines."""
    rng = np.random.default_rng(seed)
    J, W = int(rng.integers(6, 40)), int(rng.integers(2, 12))
    caps = rng.integers(8, 64, size=(J, 2)).astype(float)
    caps[1] = 2 * caps[0] if 2 * caps[0].max() <= 255 else caps[0]
    D = rng.integers(1, 9, size=(W, 2)).astype(float)
    wanted = rng.integers(1, 25, size=W).astype(float)
    phi = rng.choice([0.5, 1.0, 2.0], size=W)
    return caps, D, wanted, phi


def _bestfit_alloc(seed, crit, use_kernel, limit=None):
    caps, D, wanted, phi = _bestfit_case(seed)
    al = OnlineAllocator(2, criterion=crit, server_policy="bestfit", seed=seed)
    for j, c in enumerate(caps):
        al.add_agent(f"a{j:03d}", tuple(c))
    for n, d in enumerate(D):
        al.register(f"f{n:03d}", demand=tuple(d), wanted_tasks=int(wanted[n]),
                    phi=float(phi[n]))
    grants = al.allocate_batched(per_agent_limit=limit, use_kernel=use_kernel)
    return [(int(g.fid[1:]), int(g.agent[1:])) for g in grants]


@pytest.mark.parametrize("crit", ["drf", "tsf"])
@pytest.mark.parametrize("limit", [None, 2])
@pytest.mark.parametrize("seed", range(4))
def test_device_bestfit_epoch_matches_host_and_reference(crit, limit, seed):
    """The fused best-fit epoch runs on the device (one dispatch) and
    equals the numpy engine's grant sequence; with no per-agent limit, the
    DRF sequence also equals the plain BF-DRF reference of the benchmark."""
    pytest.importorskip("jax")
    from repro.core import engine_jax

    host = _bestfit_alloc(seed, crit, False, limit)
    d0 = engine_jax.DISPATCH_COUNT
    dev = _bestfit_alloc(seed, crit, "fused", limit)
    assert engine_jax.DISPATCH_COUNT == d0 + 1
    assert len(host) > 10 and dev == host
    if crit == "drf" and limit is None:
        caps, D, wanted, phi = _bestfit_case(seed)
        ref = _bfdrf_reference()(
            {}, D=D, tot=np.zeros(len(D)),
            wanted=wanted, phi=phi, free=caps.copy(), ctot=caps.sum(axis=0))
        assert ref == host


def _borg_grid(d):
    """Every free vector of the Borg grid [0, 256]^2 that fits ``d``."""
    a = np.stack(np.meshgrid(np.arange(257), np.arange(257), indexing="ij"),
                 axis=-1).reshape(-1, 2)
    return a[(a >= np.asarray(d)).all(axis=1)]


def test_device_bestfit_key_orders_the_borg_grid_as_float64():
    """Exhaustive: for each Borg demand, sort every fitting free vector by
    the host's float64 cosine score (ties, within the host's 1e-12, by
    index); the device's exact key must call each adjacent pair strictly
    ordered where float64 orders it, and equal where float64 ties it."""
    pytest.importorskip("jax")
    import jax
    import jax.numpy as jnp

    from repro.core import criteria, engine_jax

    @jax.jit
    def order(u2, v, k, m):
        left = engine_jax._wide_mul(u2[k], v[m])
        right = engine_jax._wide_mul(u2[m], v[k])
        return engine_jax._wide_gt(left, right), engine_jax._wide_eq(
            left, right)

    pairs = ties = 0
    for c in BORG_CPU:
        for m_ in BORG_MEM:
            d = np.array([c, m_])
            a = _borg_grid(d)
            score = criteria.bestfit_scores(a.astype(float), d.astype(float))
            idx = np.lexsort((np.arange(len(a)), score))
            tied = np.diff(score[idx]) <= 1e-12
            u2, v = engine_jax.bestfit_keys(jnp.asarray(a, jnp.int32),
                                            jnp.asarray(d, jnp.int32))
            gt, eq = order(u2, v, jnp.asarray(idx[:-1]),
                           jnp.asarray(idx[1:]))
            gt, eq = np.asarray(gt), np.asarray(eq)
            np.testing.assert_array_equal(eq, tied, err_msg=f"d={d}")
            np.testing.assert_array_equal(gt, ~tied, err_msg=f"d={d}")
            pairs += len(tied)
            ties += int(tied.sum())
    assert pairs > 3_000_000 and ties > 0


@pytest.mark.parametrize("d", [(2, 1), (16, 12), (5, 12), (3, 3)])
def test_device_bestfit_argmin_takes_the_lowest_tied_index(d):
    """On masked samples of the Borg grid (collinear free vectors tie
    exactly), the device select is the host's float64 argmin with ties to
    the lowest index."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core import criteria, engine_jax
    from repro.core.policies import argmin_masked

    rng = np.random.default_rng(sum(d))
    d = np.asarray(d)
    grid = _borg_grid(d)
    for _ in range(20):
        a = grid[rng.choice(len(grid), size=512)]
        a[rng.choice(512, 64)] = a[rng.integers(512)]       # exact repeats
        ok = rng.random(512) < 0.5
        score = criteria.bestfit_scores(a.astype(float), d.astype(float))
        want = argmin_masked(score, ok, "low", None)
        j = engine_jax.bestfit_argmin(
            *engine_jax.bestfit_keys(jnp.asarray(a, jnp.int32),
                                     jnp.asarray(d, jnp.int32)),
            jnp.asarray(ok))
        assert int(j) == want
    collinear = np.array([[9, 9], [8, 4], [4, 2], [12, 6], [6, 3]])
    j = engine_jax.bestfit_argmin(
        *engine_jax.bestfit_keys(jnp.asarray(collinear, jnp.int32),
                                 jnp.asarray([2, 1], jnp.int32)),
        jnp.asarray([True, False, True, True, True]))
    assert int(j) == 2


def test_device_bestfit_key_has_no_division_sqrt_or_matmul():
    pytest.importorskip("jax")
    import jax
    import jax.numpy as jnp

    from repro.core import engine_jax

    def prims(jaxpr, out):
        for eqn in jaxpr.eqns:
            out.add(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                prims(sub, out)
        return out

    closed = jax.make_jaxpr(lambda f, d, ok: engine_jax.bestfit_argmin(
        *engine_jax.bestfit_keys(f, d), ok))(
        jnp.zeros((16, 2), jnp.int32), jnp.ones(2, jnp.int32),
        jnp.ones(16, bool))
    used = prims(closed.jaxpr, set())
    assert "reduce" in used and "mul" in used        # the variadic select
    assert not used & {"div", "sqrt", "rsqrt", "dot_general", "rem", "pow",
                       "integer_pow", "exp", "log"}, used


@pytest.mark.parametrize("crit,metric,kw,ok", [
    ("drf", "cosine", {}, True),
    ("tsf", "cosine", {}, True),
    ("drf", "tight", {}, False),
    ("drf", "align", {}, False),
    ("rpsdsf", "cosine", {}, False),
    ("psdsf", "cosine", {}, False),
    ("drf", "cosine", {"devices": 2}, False),
])
def test_device_bestfit_coverage_is_explicit(crit, metric, kw, ok):
    """supports() covers best-fit for DRF/TSF with the cosine metric on one
    device only; the engine refuses the rest outright."""
    pytest.importorskip("jax")
    from repro.core import engine_jax

    assert engine_jax.supports(crit, "bestfit", "characterized", "low",
                               bf_metric=metric, **kw) is ok
    if ok:
        return
    with pytest.raises(ValueError, match="best-fit"):
        engine_jax.run_epoch_async(
            crit, "bestfit", X=np.zeros((1, 2)), D=np.ones((1, 2)),
            C=np.full((2, 2), 4.0), FREE=np.full((2, 2), 4.0),
            phi=np.ones(1), allowed=np.ones((1, 2), bool),
            wanted=np.array([3.0]), true_demands=np.ones((1, 2)),
            bf_metric=metric, **kw)


@pytest.mark.parametrize("cap,demand,match", [
    ((8.5, 10.0), (1.0, 1.0), "whole units"),
    ((8.0, 10.0), (1.0, 0.5), "whole units"),
    ((40000.0, 4.0), (1.0, 1.0), "orders free"),
])
def test_device_bestfit_refuses_inputs_its_key_cannot_order(cap, demand,
                                                             match):
    """Under use_kernel="fused", best-fit inputs that are not whole units
    (or exceed the key's bounds) raise before the dispatch, with the epoch
    undone; they never fall back to the host in silence."""
    pytest.importorskip("jax")
    from repro.core import engine_jax

    al = OnlineAllocator(2, criterion="drf", server_policy="bestfit", seed=0)
    al.add_agent("a0", cap)
    al.add_agent("a1", (8.0, 8.0))
    al.register("f0", demand=demand, wanted_tasks=4)
    state0 = al.rng.bit_generator.state
    d0 = engine_jax.DISPATCH_COUNT
    with pytest.raises(ValueError, match=match):
        al.allocate_batched(use_kernel="fused")
    assert engine_jax.DISPATCH_COUNT == d0
    assert al.rng.bit_generator.state == state0
    assert al.frameworks["f0"].n_tasks == 0
    assert al.allocate_batched(use_kernel=False)      # the host still serves


def test_device_bestfit_checks_only_what_the_key_can_meet():
    """Columns no wanting row may use or fit stay out of the check: an
    epoch with nothing feasible (as the benchmark's warm-up dispatches)
    runs whatever those columns hold."""
    pytest.importorskip("jax")
    from repro.core import engine_jax

    free = np.full((4, 2), 1e9)
    seq = engine_jax.run_epoch(
        "drf", "bestfit", X=np.zeros((2, 4)), D=np.ones((2, 2)), C=free,
        FREE=free, phi=np.ones(2), allowed=np.zeros((2, 4), bool),
        wanted=np.array([5.0, 0.0]), true_demands=np.ones((2, 2)))
    assert seq == []
