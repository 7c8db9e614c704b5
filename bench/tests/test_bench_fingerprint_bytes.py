"""The reader of the fingerprint's hashed bytes on a synthetic recorder,
and on a program that does not count them."""
import types

import pytest

from bench import spec
from repro.core import tracing

METRICS = ("fingerprint_mb_per_epoch.fill", "fingerprint_mb_per_epoch.churn")


def _ctx(lo, hi):
    return types.SimpleNamespace(spans=types.SimpleNamespace(
        records=[("service.drain_epoch", lo, hi)]))


def _span(rec, name, t0, t1, **attrs):
    rec._push(tracing.Record(next(rec._ids), 0, 0, name, t0, t1, attrs))


@pytest.mark.parametrize("counted", (True, False),
                         ids=("counted", "not_counted"))
@pytest.mark.parametrize("metric", METRICS)
def test_fingerprint_mb_per_epoch(monkeypatch, metric, counted):
    r = tracing.Recorder()
    monkeypatch.setattr(tracing, "RECORDER", r)
    # two epochs inside [10, 20], one outside it
    for base, nbytes in ((10.0, 1_000_000), (15.0, 3_000_000),
                         (30.0, 9_000_000)):
        attrs = {"epoch_cache.hashed_bytes": nbytes} if counted else {}
        _span(r, "epoch_cache.fingerprint", base + 0.1, base + 0.2, **attrs)
        _span(r, "online.begin_epoch", base, base + 1.0)
    got = spec.reader(metric)(_ctx(10.0, 20.0))
    assert got == (pytest.approx(2.0) if counted else None)
