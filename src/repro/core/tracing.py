"""Spans and counters of the served allocator's own layers: a bounded,
in-memory flight recorder an operator can leave on.

``span(name, **attrs)`` times one call of a layer on ``time.perf_counter``
and records its id, its parent (the innermost span open on this thread),
its root (the outermost, so every span of one epoch shares the id of the
epoch's ``service.drain_epoch``), its interval and its attributes.  While
JAX is loaded each span also opens a ``jax.profiler.TraceAnnotation`` of
the same name, so a profiler trace shows it on the host plane, on the
device trace's clock, and every idle gap of the device can be put down to
what the host was doing.

``count(name, value)`` adds to a running total and to the attributes of
the innermost open span (a zero-length record of its own when none is
open), so a count is kept where the work happened and can be read for any
window of time.  One ``jax.monitoring`` listener counts every program JAX
lowers (``jax.lowerings``, whether its binary then comes from the backend
or from the persistent cache) and the seconds spent tracing, lowering and
compiling or loading it (``jax.compile_s``).

Records go into a ring of :data:`RING` entries; the oldest are dropped and
counted.  Nothing is written anywhere.  ``set_enabled(False)`` turns the
recorder into a no-op: no record, no annotation, no count.

This module imports no JAX: the numpy-only path stays free of it.
"""
from __future__ import annotations

import collections
import functools
import itertools
import sys
import threading
import time

#: every span name the program records, outermost layer first
SPAN_NAMES = (
    "service.drain_epoch", "service.complete", "online.begin_epoch",
    "state.epoch_view", "epoch_cache.fingerprint", "engine_jax.upload",
    "online.commit_epoch", "engine_jax.result", "engine_jax.wait",
    "engine_jax.readback",
)
#: records kept before the oldest are dropped
RING = 65536

_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
#: trace, lowering, and backend compile; the last includes the time to
#: read a program from the persistent cache, so that is not added again
_COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration", _LOWERING,
    "/jax/core/compile/backend_compile_duration"))


class Record:
    """One span (``t0 < t1``) or one count made outside any span
    (``t0 == t1``, ``parent == root == 0``)."""

    __slots__ = ("id", "parent", "root", "name", "t0", "t1", "attrs")

    def __init__(self, id_, parent, root, name, t0, t1, attrs):
        self.id, self.parent, self.root = id_, parent, root
        self.name, self.t0, self.t1, self.attrs = name, t0, t1, attrs

    def __repr__(self) -> str:
        return (f"Record({self.name!r}, id={self.id}, parent={self.parent}, "
                f"root={self.root}, {1e6 * (self.t1 - self.t0):.1f} us, "
                f"{self.attrs})")


class _Span:
    __slots__ = ("_owner", "_name", "_attrs", "_rec", "_ann")

    def __init__(self, owner, name, attrs):
        self._owner, self._name, self._attrs = owner, name, attrs
        self._rec = self._ann = None

    def __enter__(self) -> Record:
        owner = self._owner
        stack = owner._stack()
        rid = next(owner._ids)
        top = stack[-1] if stack else None
        rec = self._rec = Record(rid, top.id if top else 0,
                                 top.root if top else rid, self._name,
                                 0.0, 0.0, self._attrs)
        stack.append(rec)
        prof = sys.modules.get("jax.profiler")
        if prof is not None:
            watch_compiles()
            self._ann = prof.TraceAnnotation(self._name)
            self._ann.__enter__()
        rec.t0 = time.perf_counter()
        return rec

    def __exit__(self, *exc) -> bool:
        rec = self._rec
        rec.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = self._owner._stack()
        if stack and stack[-1] is rec:
            stack.pop()
        self._owner._push(rec)
        return False


class _Off:
    """The shared span of a recorder that is off."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc) -> bool:
        return False


_OFF = _Off()


class Recorder:
    """Spans and counters in a ring of ``maxlen`` records."""

    def __init__(self, maxlen: int = RING):
        self.enabled = True
        self._ring: collections.deque = collections.deque(maxlen=maxlen)
        self._totals: dict = {}
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        self.dropped = 0
        self._lost_t1 = float("-inf")   # newest end time among dropped

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _push(self, rec: Record) -> None:
        with self._lock:
            ring = self._ring
            if len(ring) == ring.maxlen:
                self.dropped += 1
                self._lost_t1 = max(self._lost_t1, ring[0].t1)
            ring.append(rec)

    def span(self, name: str, **attrs):
        """Context manager timing one call; yields its :class:`Record`
        (None while the recorder is off)."""
        if not self.enabled:
            return _OFF
        return _Span(self, name, attrs)

    def count(self, name: str, value=1) -> None:
        """Add ``value`` to counter ``name``."""
        if not self.enabled:
            return
        stack = self._stack()
        with self._lock:
            self._totals[name] = self._totals.get(name, 0) + value
            if stack:
                attrs = stack[-1].attrs
                attrs[name] = attrs.get(name, 0) + value
                return
        now = time.perf_counter()
        self._push(Record(next(self._ids), 0, 0, name, now, now,
                          {name: value}))

    def records(self, lo: float = float("-inf"),
                hi: float = float("inf")) -> list:
        """The records that lie wholly inside ``[lo, hi]``, oldest end
        first."""
        with self._lock:
            ring = list(self._ring)
        return [r for r in ring if r.t0 >= lo and r.t1 <= hi]

    def lost_since(self, t: float) -> bool:
        """Whether a record that ended at or after ``t`` was dropped."""
        return self._lost_t1 >= t

    def totals(self) -> dict:
        """Every counter's running total, and ``dropped``."""
        with self._lock:
            out = dict(self._totals)
        out["dropped"] = self.dropped
        return out

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._totals.clear()
            self.dropped = 0
            self._lost_t1 = float("-inf")


#: the process's recorder
RECORDER = Recorder()


def span(name: str, **attrs):
    return RECORDER.span(name, **attrs)


def count(name: str, value=1) -> None:
    RECORDER.count(name, value)


def records(lo: float = float("-inf"), hi: float = float("inf")) -> list:
    return RECORDER.records(lo, hi)


def lost_since(t: float) -> bool:
    return RECORDER.lost_since(t)


def totals() -> dict:
    return RECORDER.totals()


def reset() -> None:
    RECORDER.reset()


def set_enabled(on: bool) -> None:
    RECORDER.enabled = bool(on)


def traced(name: str):
    """Decorator: each call of the function is one span ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kw):
            with RECORDER.span(name):
                return fn(*args, **kw)
        return call
    return wrap


def _on_compile_event(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        if event == _LOWERING:
            count("jax.lowerings", 1)
        count("jax.compile_s", duration)


_watching = False


def watch_compiles() -> None:
    """Register the compile listener with ``jax.monitoring`` (once per
    process; a no-op until JAX is loaded)."""
    global _watching
    if _watching:
        return
    monitoring = sys.modules.get("jax.monitoring")
    if monitoring is None:
        return
    monitoring.register_event_duration_secs_listener(_on_compile_event)
    _watching = True


watch_compiles()
