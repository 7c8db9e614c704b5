"""The allocator's device programs, compiled for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached, and refuses what the chip would refuse
(unaligned Pallas blocks, unsupported lowerings, programs that do not fit).
The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.  The persistent compilation cache is
off around these compiles: what they would write cannot be read back
without a chip.

Also here, on the CPU backend: ``chip_smoke.py --rehearse`` in-process,
and the smoke failing when the device path falls back to the host.
"""
from __future__ import annotations

import importlib.util
import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import engine_jax  # noqa: E402

#: device memory of one v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9
MAX_STEPS = 16384          # the step bucket of a ~10^4-grant epoch
R = 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _epoch_args(sharding, N, J, perm_rows=1):
    """Abstract ``epoch_loop`` arguments at padded shape (N, J)."""
    f32, i32 = jnp.float32, jnp.int32

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (S((N, J), f32), S((N, R), f32), S((N, R), f32), S((J, R), f32),
            S((J, R), f32), S((N,), f32), S((N,), f32), S((N, J), jnp.bool_),
            S((perm_rows, J), i32), S((J,), i32), S((), i32), S((), i32),
            S((), i32), S((), i32), S((), f32))


@pytest.mark.parametrize("kind,policy", [("rpsdsf", "pooled"), ("drf", "rrr"),
                                         ("drf", "bestfit")])
@pytest.mark.parametrize("N,J", [(2048, 1024), (2048, 16384)])
def test_epoch_loop_compiles_for_v5e(one_chip, kind, policy, N, J):
    """The served fused epoch (donated buffers, as on the chip) at the
    2000x1000 fleet point and the 10^4-agent bucket fits one chip."""
    rows = (engine_jax.rrr_perm_budget(MAX_STEPS, J) if policy == "rrr"
            else 1)
    compiled = engine_jax._jitted(True).lower(
        *_epoch_args(one_chip, N, J, rows), kind=kind, policy=policy,
        lookahead=False, use_limit=False, max_steps=MAX_STEPS).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes >= N * J * 4   # X alone, f32
    assert total < V5E_HBM_BYTES // 8, total


@pytest.mark.parametrize("N,J", [(2048, 1024), (2048, 16384)])
def test_psdsf_score_kernels_compile_for_v5e(one_chip, N, J):
    """The three Pallas reductions lower to Mosaic kernels at the fleet
    point and at the 10^4-agent bucket, where the per-tile results held in
    scalar memory are largest (16 x 128 tiles)."""
    from repro.kernels.psdsf_score import kernel as K

    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = {
        "1d": jax.jit(lambda s, ok: K.masked_argmin1d_tiles(s, ok)).lower(
            S((N,)), S((N,), jnp.int32)),
        "2d": jax.jit(lambda s, f: K.masked_argmin2d_tiles(s, f)).lower(
            S((N, J)), S((N, J), jnp.int32)),
        "psdsf": jax.jit(lambda x, p, d, r: K.psdsf_argmin_tiles(
            x, p, d, r)).lower(S((N,)), S((N,)), S((N, R)), S((J, R))),
    }
    for name, lo in lowered.items():
        assert "tpu_custom_call" in lo.compile().as_text(), name


@pytest.mark.parametrize("kind,policy", [("rpsdsf", "pooled"), ("drf", "rrr")])
def test_mesh_epoch_compiles_for_four_v5e_chips(topo, monkeypatch, kind,
                                                policy):
    """The shard_map epoch over a 4-chip agent mesh: per-device memory fits
    and the per-grant partials cross chips as collectives."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.launch import mesh as mesh_mod

    mesh = Mesh(np.array(topo.devices[:4]), ("agents",))
    monkeypatch.setattr(mesh_mod, "make_agent_mesh", lambda n: mesh)
    N, J = 2048, 1024
    rows = (engine_jax.rrr_perm_budget(MAX_STEPS, J) if policy == "rrr"
            else 1)
    compiled = engine_jax._jitted_mesh().lower(
        *_epoch_args(NamedSharding(mesh, PartitionSpec()), N, J, rows),
        kind=kind, policy=policy, lookahead=False, use_limit=False,
        max_steps=MAX_STEPS, devices=4).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < V5E_HBM_BYTES // 8


# ---------------------------------------------------------------------------
# chip_smoke.py on the CPU backend
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_rehearsal_passes(smoke, capsys):
    assert smoke.main(["--rehearse"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    phases = [json.loads(l[len("phase "):]) for l in out
              if l.startswith("phase ")]
    assert [p["phase"] for p in phases] == ["A", "B", "C"]
    assert all(p["dispatches"] > 0 and p["oracle_parity"] for p in phases)
    assert phases[0]["cache_hits"] >= 2
    assert json.loads(out[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": len(jax.devices())}}


def test_chip_smoke_refuses_a_process_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_chip_smoke_fails_on_host_fallback(smoke, monkeypatch, capsys):
    """Every fused dispatch fails, so the allocator heals on the host:
    grants still flow, but the smoke must exit non-zero and name it."""
    from repro.core import engine, faults

    monkeypatch.setitem(engine.AUTO_KERNEL_MIN_CELLS, "cpu", 1)
    monkeypatch.setattr(smoke, "REHEARSE_SIZES",
                        {"A": (48, 200), "B": (48, 24), "C": (48, 200)})
    new_service = smoke._new_service

    def faulty(*args, **kw):
        svc = new_service(*args, **kw)
        svc.alloc.fault_injector = faults.EngineFaultInjector(
            fail_dispatches=10**6)
        svc.alloc.recovery = faults.RecoveryPolicy(max_retries=0,
                                                   backoff_s=0.0)
        return svc

    monkeypatch.setattr(smoke, "_new_service", faulty)
    assert smoke.main(["--rehearse"]) == 1
    cap = capsys.readouterr()
    assert '"ok": true' not in cap.out
    assert "host_fallbacks" in cap.err
    assert "injected device fault" in cap.err
