"""Drive the served allocator through one cell: set-up, window, grace.

The window calls only the service's own entry points
(``AllocatorService.submit`` / ``drain_epoch`` / ``complete``), from the
client's side, and times them on the host clock.  With spans on, wrappers
around the program's layer entry points record each call's host interval
and open a profiler annotation of the same name, so that device idle gaps
can be laid against what the host was doing.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import heapq
import time

import numpy as np

from bench import ledger, traffic

#: (span name, module, class, attribute) of each wrapped entry point
SPAN_POINTS = (
    ("service.drain_epoch", "repro.launch.alloc_serve", "AllocatorService",
     "drain_epoch"),
    ("service.complete", "repro.launch.alloc_serve", "AllocatorService",
     "complete"),
    ("online.begin_epoch", "repro.core.online", "OnlineAllocator",
     "begin_epoch"),
    ("epoch_cache.fingerprint", "repro.core.epoch_cache", "EpochCache",
     "fingerprint"),
    ("online.commit_epoch", "repro.core.online", "OnlineAllocator",
     "commit_epoch"),
    ("engine_jax.result", "repro.core.engine_jax", "EpochHandle", "result"),
)


class Spans:
    """Host intervals of the wrapped layer entry points (``perf_counter``)."""

    def __init__(self):
        self.records: list = []       # (name, t0, t1)

    def total(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.records if n == name)

    def count(self, name: str) -> int:
        return sum(1 for n, _, _ in self.records if n == name)

    @contextlib.contextmanager
    def installed(self):
        import importlib

        from jax.profiler import TraceAnnotation

        saved = []
        rec = self.records

        def wrap(name, fn):
            def wrapped(*args, **kw):
                t0 = time.perf_counter()
                try:
                    with TraceAnnotation(name):
                        return fn(*args, **kw)
                finally:
                    rec.append((name, t0, time.perf_counter()))
            return wrapped

        for name, mod, cls, attr in SPAN_POINTS:
            owner = getattr(importlib.import_module(mod), cls)
            raw = owner.__dict__[attr]
            saved.append((owner, attr, raw))
            if isinstance(raw, staticmethod):
                setattr(owner, attr, staticmethod(wrap(name, raw.__func__)))
            else:
                setattr(owner, attr, wrap(name, raw))
        try:
            yield self
        finally:
            for owner, attr, raw in saved:
                setattr(owner, attr, raw)


@dataclasses.dataclass
class Outcome:
    """What a run measured and logged."""
    window_s: float
    grants: int
    epochs: int
    attempted: int
    failed: int
    latencies_ms: object             # per request (open loop), else None
    lateness_ms: list
    released_executors: int
    epoch_shapes: list               # [(frameworks, machines, grants)]
    counters: dict
    occupancy: float                 # dominant-resource share held at start
    rounds_s: list = dataclasses.field(default_factory=list)


def _dominant_occupancy(service, agents) -> float:
    cap = np.asarray([c for _, c in agents]).sum(axis=0)
    free = np.asarray(list(service.alloc.free.values())).sum(axis=0)
    return float(((cap - free) / cap).max())


#: the keyword arguments of ``AllocatorService`` that the harness sets; a
#: configuration's ``service`` object may give any other
SERVICE_SET = ("n_resources", "agents", "criterion", "server_policy",
               "use_kernel", "epoch_cache", "seed")


def new_service(cell, agents, seed: int):
    """The service of the cell's configuration: the :data:`SERVICE_SET`
    keys, then the settings of its ``service`` object."""
    from repro.launch import alloc_serve

    cfg = cell.config
    return alloc_serve.AllocatorService(
        len(cfg["resources"]), agents, criterion=cfg["criterion"],
        server_policy=cfg["server_policy"], use_kernel=cfg["use_kernel"],
        epoch_cache=cfg["epoch_cache"], seed=seed, **cfg.get("service", {}))


def _submit(service, log, req):
    from repro.launch.alloc_serve import AllocRequest

    service.submit(AllocRequest(req.fid, req.demand, req.n_executors,
                                phi=req.phi))
    log.register(req.fid, req.demand, req.n_executors, req.phi)


def _drain(service, log, checked: bool):
    state = copy.deepcopy(service.alloc.rng.bit_generator.state)
    grants = service.drain_epoch()
    pairs = [(g.fid, g.agent) for g in grants]
    log.epoch(state, pairs, checked)
    return pairs


def _complete(service, log, fid) -> int:
    fw = service.alloc.frameworks.get(fid)
    n = 0 if fw is None else fw.n_tasks
    service.complete(fid)
    log.complete(fid)
    return n


class _Counters:
    """Program counters read as deltas over the window."""

    def __init__(self, service):
        from repro.core import engine_jax

        self.engine, self.service = engine_jax, service
        self.start = self._read()

    def _read(self) -> dict:
        cache = self.service.alloc.epoch_cache
        stats = cache.stats() if cache is not None else {}
        return {"traces": self.engine.TRACE_COUNT,
                "dispatches": self.engine.DISPATCH_COUNT,
                "cache_hits": stats.get("hits", 0),
                "cache_misses": stats.get("misses", 0)}

    def delta(self) -> dict:
        now = self._read()
        out = {k: now[k] - self.start[k] for k in now}
        faults = dict(self.service.alloc.fault_counters())
        faults["epoch_retries"] = self.service.epoch_retries
        faults["epoch_failures"] = self.service.epoch_failures
        out["faults_nonzero"] = {k: v for k, v in faults.items() if v}
        return out


def set_up(cell, seed: int, seconds: float):
    """The roster, the service, the standing or steady load and the
    traffic of the window.  Returns ``(service, log, plan)``."""
    mix, cfg = cell.traffic, cell.config
    agents = traffic.roster(cfg, seed)
    log = ledger.Log(agents)
    service = new_service(cell, agents, seed)
    plan: dict = {"agents": agents}
    if mix["loop"] == "rounds":
        frameworks, places = traffic.standing(mix, cfg, agents, seed)
    elif mix["loop"] == "poisson":
        steady, places = traffic.steady(mix, cfg, agents, seed)
        frameworks = [(r.fid, r.demand, r.n_executors, r.phi)
                      for r, _ in steady]
        plan["steady"] = steady
        plan["arrivals"] = traffic.arrivals(mix, cfg, seconds, seed)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    by_fid: dict = {}
    for p in places:
        by_fid.setdefault(p[0], []).append(p)
    for fid, demand, wanted, phi in frameworks:
        service.alloc.register(fid, demand=demand, wanted_tasks=wanted,
                               phi=phi)
        for f, agent, n in by_fid[fid]:
            service.alloc.force_place(f, agent, n)
        log.place(fid, demand, wanted, phi, by_fid[fid])
    plan["occupancy"] = _dominant_occupancy(service, agents)
    return service, log, plan


def run_rounds(cell, service, log, plan, seed, seconds, spans=None,
               annotate=None) -> Outcome:
    """Closed loop: each round submits a fresh batch, drains one epoch and
    completes the batch.  The window is whole rounds: from the first
    round's submit to the end of the first round that ends at or after
    ``seconds``."""
    mix, cfg = cell.traffic, cell.config
    agents = plan["agents"]
    counters = _Counters(service)
    grants = epochs = attempted = failed = released = 0
    shapes: list = []
    rounds_s: list = []
    rnd = 0
    batch = traffic.batch(mix, cfg, seed, rnd)
    ctx = spans.installed() if spans is not None else contextlib.nullcontext()
    with ctx, (annotate or contextlib.nullcontext)():
        t0 = t_round = time.perf_counter()
        while True:
            for req in batch:
                _submit(service, log, req)
            pairs = _drain(service, log, checked=True)
            # the rows the epoch ran over: the queue is applied in the drain
            n_fw = len(service.alloc.frameworks)
            grants += len(pairs)
            epochs += 1
            attempted += len(batch)
            failed += len({r.fid for r in batch} - {f for f, _ in pairs})
            shapes.append((n_fw, len(agents), len(pairs)))
            for req in batch:
                released += _complete(service, log, req.fid)
            t_end = time.perf_counter()
            rounds_s.append(t_end - t_round)
            t_round = t_end
            if t_end - t0 >= seconds:
                break
            rnd += 1
            batch = traffic.batch(mix, cfg, seed, rnd)
    return Outcome(t_end - t0, grants, epochs, attempted, failed, None, [],
                   released, shapes, counters.delta(), plan["occupancy"],
                   rounds_s)


def warm_up(cell, service, log, plan, seed) -> int:
    """Before the window, the service's first-epoch costs on requests of
    its own.  Closed loop: a batch's worth of frameworks registered and
    deregistered, so the state arrays grow to the window's size with no
    epoch run.  Open loop: one real epoch of a second's arrivals, completed
    at once; logged, applied, not compared.  Returns the grants made."""
    mix = cell.traffic
    if mix["loop"] == "rounds":
        reqs = traffic.batch(mix, cell.config, seed, -1)
        for req in reqs:
            service.alloc.register(req.fid, demand=req.demand,
                                   wanted_tasks=req.n_executors, phi=req.phi)
        for req in reqs:
            service.complete(req.fid)
        return 0
    reqs = [traffic.Request("w" + r.fid, r.demand, r.n_executors, r.phi)
            for _, r, _ in plan["arrivals"][:int(mix["rate_rps"])]]
    for req in reqs:
        _submit(service, log, req)
    pairs = _drain(service, log, checked=False)
    for req in reqs:
        _complete(service, log, req.fid)
    return len(pairs)


def run_poisson(cell, service, log, plan, seed, seconds, spans=None,
                annotate=None) -> Outcome:
    """Open loop: requests are submitted when due, an epoch is drained
    whenever the queue holds a request (or a framework still wants
    executors after a release), and every framework completes its hold
    after its first executor.  After the window, epochs go on (no new
    arrivals) until every request due in it has an executor, or
    ``grace_s`` has passed."""
    mix = cell.traffic
    agents = plan["agents"]
    arrivals = plan["arrivals"]
    counters = _Counters(service)
    done: list = []                         # (time, fid) heap
    first: dict = {}                        # fid -> first commit time
    due: dict = {}
    hold = {req.fid: h for _, req, h in arrivals}
    lateness = []
    grants = epochs = released = 0
    shapes: list = []
    pending: set = set()                    # submitted, not fully granted
    freed = False                           # a release since the last epoch
    ctx = spans.installed() if spans is not None else contextlib.nullcontext()
    i = 0
    t0 = time.perf_counter()
    for req, residual in plan["steady"]:
        heapq.heappush(done, (t0 + residual, req.fid))
    end = t0 + seconds
    grace_end = end + float(mix["grace_s"])
    in_window = True
    with contextlib.ExitStack() as stack:
        stack.enter_context(ctx)
        stack.enter_context((annotate or contextlib.nullcontext)())
        while True:
            now = time.perf_counter()
            if in_window and now >= end:
                in_window = False
                stack.close()           # spans and annotation off
            if not in_window and (len(first) == len(arrivals)
                                  or now >= grace_end):
                break
            while done and done[0][0] <= now:
                _, fid = heapq.heappop(done)
                n = _complete(service, log, fid)
                if in_window:
                    released += n
                freed = True
            queued = False
            while i < len(arrivals) and t0 + arrivals[i][0] <= now:
                off, req, _ = arrivals[i]
                due[req.fid] = t0 + off
                lateness.append(1e3 * (now - due[req.fid]))
                _submit(service, log, req)
                pending.add(req.fid)
                queued = True
                i += 1
            if queued or (pending and freed):
                pairs = _drain(service, log, checked=True)
                t_commit = time.perf_counter()
                n_fw = len(service.alloc.frameworks)
                freed = False
                for fid, _ in pairs:
                    if fid not in first:
                        first[fid] = t_commit
                        heapq.heappush(done, (t_commit + hold[fid], fid))
                fws = service.alloc.frameworks
                pending = {f for f in pending
                           if f in fws and fws[f].n_tasks < fws[f].wanted_tasks}
                if in_window and t_commit <= end:
                    grants += len(pairs)
                    epochs += 1
                    shapes.append((n_fw, len(agents), len(pairs)))
                continue
            nxt = min([end if in_window else grace_end]
                      + ([t0 + arrivals[i][0]] if i < len(arrivals) else [])
                      + ([done[0][0]] if done else []))
            time.sleep(max(0.0, min(nxt - time.perf_counter(), 0.05)))
    lat = [1e3 * (first[f] - due[f]) for f in due if f in first]
    return Outcome(seconds, grants, epochs, len(arrivals),
                   len(arrivals) - len(first), lat, lateness, released,
                   shapes, counters.delta(), plan["occupancy"])


LOOPS = {"rounds": run_rounds, "poisson": run_poisson}
