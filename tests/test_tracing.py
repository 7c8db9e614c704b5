"""The program's span-and-counter recorder (``repro.core.tracing``): ids,
parents and roots, counters on the innermost span, the ring, the switch,
the compile listener, and the spans of one served epoch on each fused
path."""
import glob
import os

import numpy as np
import pytest

from repro.core import tracing
from repro.launch import alloc_serve

#: (span, parent span) of one served fused epoch that misses the cache
EPOCH_TREE = {
    "service.drain_epoch": None,
    "online.begin_epoch": "service.drain_epoch",
    "state.epoch_view": "online.begin_epoch",
    "epoch_cache.fingerprint": "online.begin_epoch",
    "engine_jax.upload": "online.begin_epoch",
    "online.commit_epoch": "service.drain_epoch",
    "engine_jax.result": "online.commit_epoch",
    "engine_jax.wait": "engine_jax.result",
    "engine_jax.readback": "engine_jax.result",
}


@pytest.fixture
def recorder(monkeypatch):
    """A fresh process recorder, switched on, for one test."""
    rec = tracing.Recorder()
    monkeypatch.setattr(tracing, "RECORDER", rec)
    return rec


def test_spans_record_parent_root_and_interval(recorder):
    with tracing.span("a", epoch=3) as a:
        with tracing.span("b") as b:
            with tracing.span("c") as c:
                pass
        with tracing.span("d") as d:
            pass
    with tracing.span("e") as e:
        pass
    assert (a.parent, a.root, a.attrs) == (0, a.id, {"epoch": 3})
    assert (b.parent, b.root) == (a.id, a.id)
    assert (c.parent, c.root) == (b.id, a.id)
    assert (d.parent, d.root) == (a.id, a.id)
    assert (e.parent, e.root) == (0, e.id)
    # children end before their parents, so the ring holds them first
    assert [r.name for r in tracing.records()] == list("cbdae")
    for child, parent in ((b, a), (c, b), (d, a)):
        assert parent.t0 <= child.t0 < child.t1 <= parent.t1
    assert b.t1 <= d.t0


def test_self_time_is_the_span_less_its_children(recorder):
    with tracing.span("outer") as outer:
        with tracing.span("inner") as inner:
            sum(range(20000))
        sum(range(20000))
    recs = tracing.records()
    kids = sum(r.t1 - r.t0 for r in recs if r.parent == outer.id)
    own = (outer.t1 - outer.t0) - kids
    assert kids == pytest.approx(inner.t1 - inner.t0)
    assert 0 < own < outer.t1 - outer.t0


def test_counts_land_on_the_innermost_open_span(recorder):
    with tracing.span("outer") as outer:
        tracing.count("bytes", 10)
        with tracing.span("inner") as inner:
            tracing.count("bytes", 5)
            tracing.count("bytes", 2)
            tracing.count("n")
    tracing.count("bytes", 100)        # none open: a record of its own
    assert outer.attrs == {"bytes": 10}
    assert inner.attrs == {"bytes": 7, "n": 1}
    alone = tracing.records()[-1]
    assert (alone.name, alone.attrs, alone.parent) == ("bytes",
                                                       {"bytes": 100}, 0)
    assert alone.t0 == alone.t1
    assert tracing.totals() == {"bytes": 117, "n": 1, "dropped": 0}
    assert sum(r.attrs.get("bytes", 0) for r in tracing.records()) == 117


def test_a_recorder_that_is_off_records_nothing(recorder):
    tracing.set_enabled(False)
    try:
        with tracing.span("x") as got:
            tracing.count("bytes", 4)
        assert got is None
        assert tracing.span("y") is tracing.span("z")   # one shared no-op
        assert tracing.records() == []
        assert tracing.totals() == {"dropped": 0}
    finally:
        tracing.set_enabled(True)
    with tracing.span("x"):
        pass
    assert [r.name for r in tracing.records()] == ["x"]


def test_the_ring_drops_the_oldest_and_counts_them(monkeypatch):
    rec = tracing.Recorder(maxlen=4)
    monkeypatch.setattr(tracing, "RECORDER", rec)
    ends = []
    for i in range(6):
        with tracing.span(f"s{i}") as r:
            pass
        ends.append(r.t1)
    assert [r.name for r in tracing.records()] == ["s2", "s3", "s4", "s5"]
    assert tracing.totals()["dropped"] == 2
    # s1 was dropped: a window from its end on has lost a record
    assert tracing.lost_since(ends[1])
    assert not tracing.lost_since(ends[2])
    tracing.reset()
    assert tracing.records() == [] and tracing.totals() == {"dropped": 0}
    assert not tracing.lost_since(ends[1])


def test_records_of_a_window(recorder):
    with tracing.span("before") as before:
        pass
    with tracing.span("inside") as inside:
        pass
    with tracing.span("after"):
        pass
    got = tracing.records(inside.t0, inside.t1)
    assert [r.name for r in got] == ["inside"]
    assert [r.name for r in tracing.records(before.t0, inside.t1)] == [
        "before", "inside"]


def test_the_compile_listener_counts_each_new_lowering_once(recorder):
    import jax.numpy as jnp

    tracing.watch_compiles()
    x = jnp.arange(977, dtype=jnp.int32)
    before = tracing.totals().get("jax.lowerings", 0)
    with tracing.span("first") as first:
        np.asarray(x[:613])             # an eager slice: a new program
    with tracing.span("again") as again:
        np.asarray(x[:613])             # the same program, cached
    assert first.attrs["jax.lowerings"] == 1
    assert first.attrs["jax.compile_s"] > 0
    assert "jax.lowerings" not in again.attrs
    assert tracing.totals()["jax.lowerings"] == before + 1


def _service(policy, criterion, n_agents=10):
    agents = [(f"a{j}", (16.0, 64.0)) for j in range(n_agents)]
    return alloc_serve.AllocatorService(
        2, agents, criterion=criterion, server_policy=policy,
        use_kernel="fused", seed=3)


def _epoch(service, n_fw=5, executors=3, prefix="f"):
    for i in range(n_fw):
        service.submit(alloc_serve.AllocRequest(f"{prefix}{i}", (1.0, 2.0),
                                                executors))
    return service.drain_epoch()


def _pow2(n, lo=8):
    return max(lo, 1 << (n - 1).bit_length())


@pytest.mark.parametrize("policy,criterion", [("rrr", "drf"),
                                              ("pooled", "rpsdsf")])
def test_a_served_epoch_has_the_span_tree_and_counts_its_bytes(
        recorder, policy, criterion):
    from repro.core import engine_jax

    service = _service(policy, criterion)
    # a first epoch places executors, so the measured one stages an X with
    # a support: one non-zero per (framework, machine) pair it holds
    held = _epoch(service, 2, 2, prefix="w")
    nonzeros = len({(g.fid, g.agent) for g in held})
    tracing.reset()
    n_fw, executors, J, R = 5, 3, 10, 2
    grants = _epoch(service, n_fw, executors)
    assert len(grants) == n_fw * executors
    service.complete("f0")
    recs = tracing.records()
    by_id = {r.id: r for r in recs}
    epoch = [r for r in recs if r.name != "service.complete"]
    assert sorted(r.name for r in epoch) == sorted(EPOCH_TREE)
    root = next(r for r in epoch if r.name == "service.drain_epoch")
    assert root.attrs["epoch"] == 1
    for r in epoch:
        want = EPOCH_TREE[r.name]
        assert (by_id[r.parent].name if r.parent else None) == want
        assert r.root == root.id
    done = next(r for r in recs if r.name == "service.complete")
    assert done.parent == 0 and done.root == done.id

    # bytes handed to the device, from the padded shapes: X's support as
    # one chunk of int32 flat indices and f32 values (a chunk is at most
    # Np * Jp entries); allowed at one bit per cell over (Np, Jp); D, TD
    # (Np, R) and C, FREE (Jp, R) f32; phi, wanted (Np,) f32; used (Jp,)
    # i32; the permutation stack (K, Jp) i32; pidx, pos, J, limit, eps at 4
    # bytes each
    Np, Jp = _pow2(2 + n_fw), _pow2(J)
    chunk = min(engine_jax.STAGE_CHUNK, Np * Jp)
    # capacity is ample and the held frameworks want nothing more, so the
    # grant bound is the new deficit; RRR stacks one permutation per J
    # grants plus four of slack, pow2-rounded
    K = _pow2(4 + 4 * -(-n_fw * executors // J)) if policy == "rrr" else 1
    expect = (8 * chunk + Np * Jp // 8 + 2 * 4 * Np * R + 2 * 4 * Jp * R
              + 2 * 4 * Np + 4 * Jp + 4 * K * Jp + 5 * 4)
    upload = next(r for r in epoch if r.name == "engine_jax.upload")
    assert upload.attrs["engine_jax.upload_bytes"] == expect
    assert tracing.totals()["engine_jax.upload_bytes"] == expect
    assert nonzeros > 0
    assert upload.attrs["engine_jax.staged_nonzeros"] == nonzeros
    assert tracing.totals()["engine_jax.staged_nonzeros"] == nonzeros


def test_an_epoch_cache_hit_uploads_nothing(recorder):
    service = _service("pooled", "rpsdsf")
    _epoch(service)
    for fid in list(service.alloc.frameworks):
        service.complete(fid)
    tracing.reset()
    _epoch(service)                     # the same profile again: a hit
    names = {r.name for r in tracing.records()}
    assert service.alloc.epoch_cache.stats()["hits"] == 1
    assert "engine_jax.upload" not in names
    assert "engine_jax.upload_bytes" not in tracing.totals()


@pytest.mark.parametrize("policy,criterion", [("rrr", "drf"),
                                              ("pooled", "rpsdsf")])
def test_the_recorder_does_not_change_the_grants(recorder, policy,
                                                 criterion):
    runs = []
    for on in (True, False):
        tracing.set_enabled(on)
        try:
            service = _service(policy, criterion)
            runs.append([[(g.fid, g.agent) for g in _epoch(service, 6, 4,
                                                            prefix=f"e{k}")]
                         for k in range(3)])
        finally:
            tracing.set_enabled(True)
    assert runs[0] == runs[1] and runs[0][0]


def test_service_stats_report_epoch_seconds_and_trace_counters(recorder):
    service = _service("rrr", "drf")
    tracing.set_enabled(False)         # epoch_s does not need the recorder
    try:
        _epoch(service, prefix="a")
    finally:
        tracing.set_enabled(True)
    _epoch(service, prefix="b")
    stats = service.stats()
    assert "latency" not in stats
    es = stats["epoch_s"]
    assert es["count"] == 2 == len(service.epoch_s)
    assert 0 < es["p50"] <= es["p99"] <= max(service.epoch_s)
    drains = [r for r in tracing.records()
              if r.name == "service.drain_epoch"]
    assert len(drains) == 1            # the second epoch only
    assert drains[0].t1 - drains[0].t0 <= service.epoch_s[-1]
    c = service.counters()
    assert c["upload_bytes"] == tracing.totals()["engine_jax.upload_bytes"]
    assert c["staged_nonzeros"] == tracing.totals().get(
        "engine_jax.staged_nonzeros", 0)
    assert c["upload_bytes"] > 0 and c["trace_dropped"] == 0
    assert {"lowerings", "compile_s"} <= set(c)


def test_program_spans_appear_on_the_profiler_host_plane(recorder,
                                                         tmp_path):
    import jax
    from jax.profiler import ProfileData

    service = _service("rrr", "drf")
    jax.profiler.start_trace(str(tmp_path))
    try:
        _epoch(service)
        service.complete("f0")
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[-1]
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert set(tracing.SPAN_NAMES) <= names


def test_span_names_cover_every_span_the_epoch_records():
    assert set(EPOCH_TREE) | {"service.complete"} == set(tracing.SPAN_NAMES)


def _placement_view(N, J, dense, seed=0):
    """A view whose X holds ~5 executors per row (``dense=False``, as a
    churn epoch does) or one in every cell (``dense=True``)."""
    from repro.core.cluster_state import StateView

    rng = np.random.default_rng(seed)
    X = np.zeros((N, J))
    if dense:
        X[:] = rng.integers(1, 4, (N, J))
    else:
        for n in range(N):
            X[n, rng.choice(J, 5, replace=False)] = rng.integers(1, 4, 5)
    Xr = np.where(rng.random((N, J)) < 0.3, X, 0.0)
    return StateView(tuple(range(N)), tuple(range(J)), X,
                     rng.random((N, 2)), rng.random((J, 2)),
                     rng.random((J, 2)), np.ones(N), rng.random((N, J)) < 0.9,
                     np.full(N, 6.0), Xr)


@pytest.mark.parametrize("shape,dense", [((1800, 4034), False),
                                         ((64, 48), True)])
def test_the_fingerprint_counts_its_bytes_and_dense_fields(recorder, shape,
                                                           dense):
    from repro.core.epoch_cache import EpochCache

    view = _placement_view(*shape, dense)
    EpochCache.fingerprint(view, view.D, criterion="drf", policy="rrr",
                           mode="m", tie="low", engine="fused")
    (fp,) = tracing.records()
    assert fp.name == "epoch_cache.fingerprint"
    hashed = fp.attrs["epoch_cache.hashed_bytes"]
    assert hashed == tracing.totals()["epoch_cache.hashed_bytes"]
    cells = view.X.nbytes + view.Xr.nbytes + view.allowed.nbytes
    # bytes follow the content: an index and a value per non-zero
    nnz = np.count_nonzero(view.X) + np.count_nonzero(view.Xr)
    assert hashed > 12 * nnz + view.allowed.size / 8
    if dense:
        assert hashed > view.X.nbytes
    else:
        assert hashed < 0.05 * cells


def test_service_counters_report_the_fingerprint_counters(recorder):
    service = _service("rrr", "drf")
    _epoch(service)
    (fp,) = [r for r in tracing.records()
             if r.name == "epoch_cache.fingerprint"]
    c = service.counters()
    assert c["hashed_bytes"] == fp.attrs["epoch_cache.hashed_bytes"] > 0
