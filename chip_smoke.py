#!/usr/bin/env python3
"""On-chip smoke of the served allocator.

Drives :class:`repro.launch.alloc_serve.AllocatorService` the way
``alloc_serve.serve`` does (``make_profiles`` request batches, the
``_AGENT_TYPES`` agent roster, R=2) at fleet size on one TPU chip:

  A  rPS-DSF / pooled, ``use_kernel="auto"``, 2000 frameworks x 1000
     agents, epoch cache on: 2 profiles x 4 rounds, so 2 epochs dispatch
     and 2 replay from the cache; ``auto`` must pick the fused device epoch.
  B  one DRF / RRR epoch, ``use_kernel="fused"``, same size (an RRR
     epoch's cache key holds its pre-drawn permutations, so a repeat
     profile later in the rng stream is a miss by design).
  C  one rPS-DSF / pooled epoch (``use_kernel="fused"``) at 2000 x 10000
     agents, the 16384-agent shape bucket.

After each phase: the device dispatch count rose, every fault counter is
zero (no retry, no host fallback, no quarantine), and the first epoch's
grant sequence equals the host oracle's: a twin service on numpy epochs
(``use_kernel=False``) fed the same requests with the same seed.

``--chips 4`` runs only phase A's first epoch through
``begin_epoch(devices=4)`` and again with ``devices=1``, in this process,
and checks the two grant sequences are equal.  ``--rehearse`` runs the same
phases at a small size on the CPU backend; without it, a process with no
TPU backend fails.

    python chip_smoke.py [--chips 4] [--rehearse]

Earlier lines report the device, the compile-cache directory and, per
phase, dispatches, grants, cache hits, parity and set-up seconds (the
first epoch, compilation included).  The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failed check exits non-zero with the first recorded fault error.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: (frameworks, agents) per phase: the chip size, and the rehearsal size
#: (phase A's rehearsal stays at the CPU backend's auto-kernel floor so
#: ``auto`` picks the fused epoch there too)
SIZES = {"A": (2000, 1000), "B": (2000, 1000), "C": (2000, 10000)}
REHEARSE_SIZES = {"A": (256, 2048), "B": (48, 24), "C": (48, 200)}
SEED = 0


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _src_on_path() -> None:
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def _new_service(n_agents: int, criterion: str, policy: str, use_kernel,
                 epoch_cache):
    """An allocator service over the ``alloc_serve.serve`` agent roster."""
    from repro.launch import alloc_serve

    types = alloc_serve._AGENT_TYPES
    agents = [(f"a{j}", types[j % len(types)]) for j in range(n_agents)]
    return alloc_serve.AllocatorService(
        2, agents, criterion=criterion, server_policy=policy,
        epoch_cache=epoch_cache, use_kernel=use_kernel, seed=SEED)


def _serve_round(service, requests) -> list:
    """One serve round as ``alloc_serve.drive`` runs it: submit, drain one
    epoch, then complete every framework so the profile can recur."""
    for req in requests:
        service.submit(req)
    grants = service.drain_epoch()
    for fid in list(service.alloc.frameworks):
        service.complete(fid)
    return grants


def _pairs(grants) -> list:
    return [(g.fid, g.agent) for g in grants]


def oracle_first_epoch(n_agents: int, criterion: str, policy: str,
                       requests) -> list:
    """The host oracle's grant sequence for one epoch over ``requests``: a
    twin service on numpy epochs (``allocate_batched(use_kernel=False)``),
    same roster, requests and seed."""
    twin = _new_service(n_agents, criterion, policy, use_kernel=False,
                        epoch_cache=None)
    return _pairs(_serve_round(twin, requests))


class _DispatchLog:
    """Records each fused dispatch's engine settings (buffer donation,
    mesh size) by wrapping ``run_epoch_async``."""

    def __init__(self):
        self.runs = []

    @contextlib.contextmanager
    def installed(self):
        from repro.core import engine_jax

        orig = engine_jax.run_epoch_async

        def wrapped(*args, **kw):
            handle = orig(*args, **kw)
            run = handle._run
            if run is not None:
                self.runs.append({"donate": run.donate,
                                  "devices": run.devices})
            return handle

        engine_jax.run_epoch_async = wrapped
        try:
            yield self
        finally:
            engine_jax.run_epoch_async = orig


def _check_inputs(profiles) -> None:
    """Quarter-multiple demands and power-of-two capacities: binary-exact
    in f32 and f64, the property exact device/host parity rests on."""
    from repro.launch import alloc_serve

    for reqs in profiles:
        for r in reqs:
            _check(all(float(d * 4).is_integer() for d in r.demand),
                   f"demand {r.demand} of {r.fid} is not quarter multiples")
    for cap in alloc_serve._AGENT_TYPES:
        _check(all(c > 0 and float(c).is_integer()
                   and (int(c) & (int(c) - 1)) == 0 for c in cap),
               f"agent capacity {cap} is not powers of two")


def _faults_clean(service, errors: list) -> dict:
    counters = dict(service.alloc.fault_counters())
    counters["epoch_retries"] = service.epoch_retries
    counters["epoch_failures"] = service.epoch_failures
    bad = {k: v for k, v in counters.items() if v}
    first = errors[0] if errors else None
    _check(not bad, f"fault counters not zero: {bad}; first fault: {first}")
    return counters


def run_phase(name: str, n_frameworks: int, n_agents: int, criterion: str,
              policy: str, use_kernel, n_profiles: int, rounds: int,
              errors: list, log: _DispatchLog, expect_hits: int = 0) -> dict:
    """Serve ``rounds`` rounds over ``n_profiles`` profiles and check them."""
    from repro.core import engine_jax
    from repro.launch import alloc_serve

    profiles = alloc_serve.make_profiles(n_profiles, n_frameworks,
                                         seed=SEED)
    _check_inputs(profiles)
    service = _new_service(n_agents, criterion, policy, use_kernel,
                           epoch_cache=True)
    service.alloc.fault_listeners.append(
        lambda kind, info: errors.append(f"{kind}: {info.get('error')}")
        if "error" in info else None)
    if use_kernel == "auto":
        picked = service.alloc._resolve_kernel("auto", n_frameworks,
                                               n_agents, "low")
        _check(picked == "fused",
               f"phase {name}: auto resolved to {picked!r}, not 'fused'")
    d0, n_runs0 = engine_jax.DISPATCH_COUNT, len(log.runs)
    t0 = time.perf_counter()
    first = _pairs(_serve_round(service, profiles[0]))
    setup_s = time.perf_counter() - t0
    _faults_clean(service, errors)
    for r in range(1, rounds):
        _serve_round(service, profiles[r % n_profiles])
    dispatches = engine_jax.DISPATCH_COUNT - d0
    runs = log.runs[n_runs0:]
    counters = _faults_clean(service, errors)
    _check(dispatches > 0, f"phase {name}: no device dispatch")
    _check(bool(runs), f"phase {name}: no fused dispatch was recorded")
    cache = service.alloc.epoch_cache.stats()
    _check(cache["hits"] >= expect_hits,
           f"phase {name}: {cache['hits']} cache hits < {expect_hits}")
    ref = oracle_first_epoch(n_agents, criterion, policy, profiles[0])
    _check(len(first) > 0, f"phase {name}: first epoch granted nothing")
    if first != ref:
        k = next((i for i, (a, b) in enumerate(zip(first, ref)) if a != b),
                 min(len(first), len(ref)))
        raise SmokeFailure(
            f"phase {name}: grant sequence diverges from the host oracle at "
            f"grant {k} of {len(first)}/{len(ref)}: device "
            f"{first[k:k + 3]} vs host {ref[k:k + 3]}")
    return {"phase": name, "criterion": criterion, "policy": policy,
            "use_kernel": str(use_kernel), "frameworks": n_frameworks,
            "agents": n_agents, "epochs": service.epochs,
            "dispatches": dispatches, "donated": all(r["donate"] for r in runs),
            "first_epoch_grants": len(first), "grants": service.decisions,
            "cache_hits": cache["hits"], "cache_misses": cache["misses"],
            "oracle_parity": True, "setup_s": setup_s,
            "fault_counters_zero": True, "faults": counters}


def run_mesh(n_frameworks: int, n_agents: int, devices: int, errors: list,
             log: _DispatchLog) -> dict:
    """Phase A's first epoch over a ``devices`` mesh and on one device."""
    from repro.core import engine_jax
    from repro.launch import alloc_serve

    requests = alloc_serve.make_profiles(1, n_frameworks, seed=SEED)[0]
    seqs, dispatches = {}, {}
    for k in (devices, 1):
        service = _new_service(n_agents, "rpsdsf", "pooled", "fused",
                               epoch_cache=None)
        service.alloc.fault_listeners.append(
            lambda kind, info: errors.append(f"{kind}: {info.get('error')}")
            if "error" in info else None)
        for req in requests:
            service.alloc.register(req.fid, demand=req.demand,
                                   wanted_tasks=req.n_executors, phi=req.phi)
        d0, n_runs0 = engine_jax.DISPATCH_COUNT, len(log.runs)
        epoch = service.alloc.begin_epoch(use_kernel="fused", devices=k)
        seqs[k] = _pairs(service.alloc.commit_epoch(epoch))
        dispatches[k] = engine_jax.DISPATCH_COUNT - d0
        _faults_clean(service, errors)
        runs = log.runs[n_runs0:]
        _check(runs and all(r["devices"] == k for r in runs),
               f"{k}-device epoch did not run on {k} devices: {runs}")
    _check(len(seqs[1]) > 0, "mesh phase granted nothing")
    _check(seqs[devices] == seqs[1],
           f"{devices}-device mesh grant sequence differs from one device "
           f"({len(seqs[devices])} vs {len(seqs[1])} grants)")
    return {"phase": f"mesh{devices}", "criterion": "rpsdsf",
            "policy": "pooled", "frameworks": n_frameworks,
            "agents": n_agents, "devices": devices,
            "dispatches": dispatches[devices],
            "grants": len(seqs[devices]), "mesh_equals_single": True,
            "fault_counters_zero": True}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the mesh-vs-one-device check of phase A")
    ap.add_argument("--rehearse", action="store_true",
                    help="small sizes on the CPU backend (no chip needed)")
    args = ap.parse_args(argv)
    _src_on_path()
    import jax

    backend = jax.default_backend()
    if backend != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (backend {backend!r}); "
              "--rehearse runs the phases on the CPU", file=sys.stderr)
        return 2
    devs = jax.devices()
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, this process has {len(devs)}", file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    print(f"compile_cache {jax.config.jax_compilation_cache_dir}",
          flush=True)
    sizes = REHEARSE_SIZES if args.rehearse else SIZES
    errors: list = []
    log = _DispatchLog()
    try:
        with log.installed():
            if args.chips > 1:
                out = [run_mesh(*sizes["A"], args.chips, errors, log)]
            else:
                out = [
                    run_phase("A", *sizes["A"], "rpsdsf", "pooled", "auto",
                              n_profiles=2, rounds=4, errors=errors,
                              log=log, expect_hits=2),
                    run_phase("B", *sizes["B"], "drf", "rrr", "fused",
                              n_profiles=1, rounds=1, errors=errors,
                              log=log),
                    run_phase("C", *sizes["C"], "rpsdsf", "pooled", "fused",
                              n_profiles=1, rounds=1, errors=errors,
                              log=log),
                ]
            for row in out:
                print("phase " + json.dumps(row), flush=True)
    except Exception as exc:
        print(f"chip_smoke: FAILED: {type(exc).__name__}: {exc}",
              file=sys.stderr)
        print(f"chip_smoke: first fault error: "
              f"{errors[0] if errors else None}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    _src_on_path()
    from repro.launch import compile_cache

    compile_cache.enable()
    sys.exit(main())
