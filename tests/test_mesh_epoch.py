"""Device-mesh allocation epochs (shard_map): the one partitioned epoch
path.

Parity contracts pinned here:

  * mesh == single device — ``epoch_loop_mesh`` grant sequences AND final
    state arrays equal the fused single-device loop bit-for-bit for every
    covered criterion x policy combo (1-device mesh in-process; true 2-
    and 8-device meshes over forced host devices in a subprocess,
    including the allocator's async begin/commit path and chained RRR
    segments with grow-and-replay), one test case per check;
  * mid-epoch exhaustion — small ``wanted`` budgets and
    ``per_agent_limit`` stop the mesh loop at exactly the reference grant
    count (the select's found-flag liveness, not the single-device loop's
    per-row feasible counts);
  * retrace discipline — a mesh (shape, devices) key retraces at most
    once; repeats reuse the cached executable.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

CRITERIA = ("drf", "tsf", "psdsf", "rpsdsf")
POLICIES = ("pooled", "rrr")


def _epoch_args(seed, N=13, J=11, R=3, wanted_hi=6):
    rng = np.random.default_rng(seed)
    D = rng.uniform(0.1, 1.0, (N, R))
    TD = D * rng.uniform(1.0, 2.0, (N, 1))
    C = rng.uniform(5.0, 10.0, (J, R))
    return dict(
        X=np.zeros((N, J)), D=D, C=C, FREE=C.copy(),
        phi=rng.uniform(0.5, 2.0, N),
        wanted=rng.integers(1, wanted_hi, N).astype(float),
        allowed=rng.random((N, J)) > 0.2, true_demands=TD,
    )


def _raw_epoch_inputs(kw, limit, max_steps=64):
    """Pack an instance dict into the positional epoch_loop argument list."""
    import jax.numpy as jnp

    J = kw["C"].shape[0]
    rng = np.random.default_rng(12)
    perms = np.stack([rng.permutation(J) for _ in range(64)]).astype(np.int32)
    return (jnp.asarray(kw["X"], jnp.float32),
            jnp.asarray(kw["D"], jnp.float32),
            jnp.asarray(kw["true_demands"], jnp.float32),
            jnp.asarray(kw["C"], jnp.float32),
            jnp.asarray(kw["FREE"], jnp.float32),
            jnp.asarray(kw["phi"], jnp.float32),
            jnp.asarray(kw["wanted"], jnp.float32),
            jnp.asarray(kw["allowed"]), jnp.asarray(perms),
            jnp.zeros(J, jnp.int32), np.int32(0), np.int32(0),
            jnp.int32(J), np.int32(limit or 0), jnp.float32(1e-9))


@pytest.mark.parametrize("crit", CRITERIA)
@pytest.mark.parametrize("pol", POLICIES)
def test_mesh_epoch_matches_fused(crit, pol):
    """1-device mesh (the same shard_map program, trivial collectives):
    grant sequence AND every returned state array bit-equal the fused
    loop."""
    pytest.importorskip("jax")
    from repro.core import engine_jax as ej

    limit = 3 if crit in ("drf", "rpsdsf") else None
    kw = _epoch_args(seed=hash((crit, pol)) % 2**31)
    args = _raw_epoch_inputs(kw, limit)
    ref = ej._jitted(False)(
        *args, kind=crit, policy=pol, lookahead=False,
        use_limit=limit is not None, max_steps=64)
    got = ej._jitted_mesh()(
        *args, kind=crit, policy=pol, lookahead=False,
        use_limit=limit is not None, max_steps=64, devices=1)
    for a, b, name in zip(ref, got,
                          "ns js count X tot FREE used pidx pos".split()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{crit}/{pol}/{name}")


@pytest.mark.parametrize("pol", POLICIES)
def test_mesh_wanted_exhaustion_and_limit(pol):
    """Tiny wanted budgets + per_agent_limit exhaust the epoch mid-budget:
    the mesh loop's found-flag liveness stops at the reference count."""
    pytest.importorskip("jax")
    from repro.core import engine_jax as ej

    kw = _epoch_args(seed=5, wanted_hi=3)       # wanted in {1, 2}
    args = _raw_epoch_inputs(kw, 2, max_steps=64)
    ref = ej._jitted(False)(
        *args, kind="rpsdsf", policy=pol, lookahead=False, use_limit=True,
        max_steps=64)
    got = ej._jitted_mesh()(
        *args, kind="rpsdsf", policy=pol, lookahead=False, use_limit=True,
        max_steps=64, devices=1)
    count = int(ref[2])
    assert 0 < count < 64                       # genuinely exhausted early
    assert int(got[2]) == count
    np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(ref[1]), np.asarray(got[1]))
    # per-agent caps respected in the sequence itself
    js = np.asarray(ref[1])[:count]
    assert np.bincount(js).max() <= 2


def test_mesh_trace_count_regression():
    """One mesh trace per (shape bucket, devices) key — repeat dispatches
    reuse the cached executable."""
    pytest.importorskip("jax")
    from repro.core import engine_jax as ej

    kw = _epoch_args(seed=9)
    args = _raw_epoch_inputs(kw, None)
    stat = dict(kind="drf", policy="pooled", lookahead=False,
                use_limit=False, max_steps=64, devices=1)
    ej._jitted_mesh()(*args, **stat)
    t0 = ej.MESH_TRACE_COUNT
    ej._jitted_mesh()(*args, **stat)             # cached: no retrace
    assert ej.MESH_TRACE_COUNT == t0
    kw2 = _epoch_args(seed=10, N=17)             # new shape: <= 1 retrace
    ej._jitted_mesh()(*_raw_epoch_inputs(kw2, None), **stat)
    assert ej.MESH_TRACE_COUNT <= t0 + 1
    ej._jitted_mesh()(*_raw_epoch_inputs(kw2, None), **stat)
    assert ej.MESH_TRACE_COUNT <= t0 + 1


_MESH8_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from repro.core.engine_jax import run_epoch_async
    from repro.core.online import OnlineAllocator

    assert len(jax.devices()) == 8, jax.devices()

    def inst(seed, N=23, J=17, R=3):
        rng = np.random.default_rng(seed)
        D = rng.uniform(0.1, 1.0, (N, R))
        TD = D * rng.uniform(1.0, 2.0, (N, 1))
        C = rng.uniform(5.0, 10.0, (J, R))
        return dict(X=np.zeros((N, J)), D=D, C=C, FREE=C.copy(),
                    phi=rng.uniform(0.5, 2.0, N),
                    wanted=rng.integers(1, 6, N).astype(float),
                    allowed=rng.random((N, J)) > 0.2, true_demands=TD)

    def report(name, ok, detail):
        print("CHECK", name, "OK" if ok else "FAIL", detail, flush=True)

    for kind in ["drf", "tsf", "psdsf", "rpsdsf"]:
        for policy in ["pooled", "rrr"]:
            limit = 3 if kind in ("drf", "rpsdsf") else None
            kw = inst(hash((kind, policy)) % 2**31)
            seqs = {k: run_epoch_async(kind, policy,
                                       rng=np.random.default_rng(7),
                                       per_agent_limit=limit, devices=k,
                                       **kw).result()
                    for k in (1, 2, 8)}
            ok = len(seqs[1]) > 0 and seqs[1] == seqs[2] == seqs[8]
            report(f"combo/{kind}/{policy}", ok,
                   {k: len(v) for k, v in seqs.items()})

    # chained segments + RRR grow-and-replay under the mesh path
    kw = inst(99)
    for kind in ["drf", "rpsdsf"]:
        a = run_epoch_async(kind, "rrr", rng=np.random.default_rng(3),
                            max_steps_cap=16, _perm_rows=2, devices=1,
                            **kw).result()
        b = run_epoch_async(kind, "rrr", rng=np.random.default_rng(3),
                            max_steps_cap=16, _perm_rows=2, devices=8,
                            **kw).result()
        report(f"chain+replay/{kind}", a == b and len(a) > 16,
               (len(a), len(b)))

    # allocator async begin/commit over the mesh == synchronous numpy
    def fill(crit, policy, devices, use_kernel):
        rng = np.random.default_rng(11)
        al = OnlineAllocator(2, criterion=crit, server_policy=policy,
                             mode="characterized", seed=0)
        for j in range(9):
            al.add_agent(f"a{j}", rng.uniform(6.0, 12.0, 2))
        for n in range(7):
            al.register(f"f{n}", demand=rng.uniform(0.5, 2.0, 2),
                        wanted_tasks=6, phi=float(rng.uniform(0.5, 2.0)))
        epoch = al.begin_epoch(use_kernel=use_kernel, devices=devices)
        return [(g.fid, g.agent) for g in al.commit_epoch(epoch)]

    for crit, policy in [("rpsdsf", "pooled"), ("drf", "rrr")]:
        ref = fill(crit, policy, 1, False)
        got = fill(crit, policy, 8, "fused")
        report(f"begin-commit/{crit}/{policy}", ref == got and len(ref) > 0,
               (len(ref), len(got)))
""")

MESH8_CHECKS = (
    [f"combo/{k}/{p}" for k in CRITERIA for p in POLICIES]
    + [f"chain+replay/{k}" for k in ("drf", "rpsdsf")]
    + [f"begin-commit/{c}/{p}" for c, p in (("rpsdsf", "pooled"),
                                            ("drf", "rrr"))])


@pytest.fixture(scope="module")
def mesh8_run():
    """The 8-forced-host-device subprocess, run once for every check (the
    device count locks at first jax init): its ``CHECK`` lines by name."""
    pytest.importorskip("jax")
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _MESH8_SCRIPT],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    checks = {}
    for line in out.stdout.splitlines():
        if line.startswith("CHECK "):
            _tag, name, verdict, detail = line.split(" ", 3)
            checks[name] = (verdict, detail)
    return out, checks


@pytest.mark.parametrize("check", MESH8_CHECKS)
def test_mesh_epoch_parity_on_8_devices(mesh8_run, check):
    """True multi-device meshes in a subprocess: each covered combo on 2
    and 8 devices, chained+replayed RRR segments on 8, and the allocator's
    async begin/commit path on 8 equal the single-device engine."""
    out, checks = mesh8_run
    assert check in checks, (
        f"check {check} did not report (rc {out.returncode})\n"
        f"stdout:\n{out.stdout[-2000:]}\nstderr:\n{out.stderr[-3000:]}")
    verdict, detail = checks[check]
    assert verdict == "OK", f"{check}: {detail}"
