"""Reduce a profiler trace to device busy time, idle share, time per device
program, and idle gaps laid against the host spans open during them.

The trace is reduced to plain tuples first (:func:`load`), so the
arithmetic below is tested on small synthetic traces.
"""
from __future__ import annotations

import dataclasses
import glob
import os

#: the host annotation around the measured window
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    device_ops: list       # (device, name, start_ns, end_ns) per operation
    device_modules: list   # (device, name, start_ns, end_ns) per program run
    host_spans: list       # (name, start_ns, end_ns) benchmark annotations
    n_devices: int


def load(log_dir: str, span_names) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``.  Device planes are
    those named ``/device:TPU:<n>``; on them the ``XLA Modules`` line holds
    one event per program execution and ``XLA Ops`` one per operation."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, mods, spans = [], [], []
    wanted = set(span_names) | {WINDOW_SPAN}
    n_dev = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = n_dev
            n_dev += 1
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": mods}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append((dev, ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return Trace(ops, mods, spans, n_dev)


def window(trace: Trace):
    """(start_ns, end_ns) of the benchmark's window annotation."""
    ws = [(s, e) for n, s, e in trace.host_spans if n == WINDOW_SPAN]
    if not ws:
        raise ValueError("the trace holds no window annotation")
    return min(s for s, _ in ws), max(e for _, e in ws)


def union(intervals, lo, hi) -> list:
    """Merged ``[start, end]`` of ``(start, end)`` pairs clipped to
    [lo, hi)."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _busy_by_device(trace: Trace, lo, hi) -> list:
    events = trace.device_ops or trace.device_modules
    return [union([(s, e) for d, _, s, e in events if d == dev], lo, hi)
            for dev in range(trace.n_devices)]


def busy_ns(trace: Trace, lo, hi) -> float:
    """Time in [lo, hi) in which an operation ran, averaged over the
    devices traced (0 where the trace holds no device)."""
    per = _busy_by_device(trace, lo, hi)
    return sum(e - s for b in per for s, e in b) / max(1, len(per))


def program_ns(trace: Trace, lo, hi, contains: str = "") -> dict:
    """Device time per program (``XLA Modules`` events) in [lo, hi),
    summed over devices."""
    out: dict = {}
    for _, name, s, e in trace.device_modules:
        s, e = max(s, lo), min(e, hi)
        if e > s and contains in name:
            out[name] = out.get(name, 0) + (e - s)
    return out


def top_ops(trace: Trace, lo, hi, k: int = 10) -> list:
    """The ``k`` device operations that took most time: ``[name, s]``."""
    tot: dict = {}
    for _, name, s, e in trace.device_ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            name = name.split(" = ")[0]       # "%fusion.20 = f32[] ..." -> id
            tot[name] = tot.get(name, 0) + (e - s)
    return [[n, t / 1e9] for n, t in
            sorted(tot.items(), key=lambda x: -x[1])[:k]]


def _innermost_time(spans, lo, hi) -> dict:
    """Seconds of [lo, hi) during which each span was the innermost (the
    latest-started) one open; ``harness`` where none was."""
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    out: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        open_ = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = max(open_, key=lambda sp: sp[1])[0] if open_ else "harness"
        out[name] = out.get(name, 0) + (b - a)
    return out


def idle_gaps(trace: Trace, lo, hi, k: int = 10) -> list:
    """The ``k`` longest idle gaps of the first device in [lo, hi), each
    named by the host span that was innermost for most of it (``harness``
    where none was open): ``[name, s]``."""
    per = _busy_by_device(trace, lo, hi)
    busy = per[0] if per else []
    gaps, prev = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        spans = [sp for sp in trace.host_spans
                 if sp[0] != WINDOW_SPAN and sp[1] < e and sp[2] > s]
        by = _innermost_time(spans, s, e)
        out.append([max(by, key=by.get), (e - s) / 1e9])
    return out
