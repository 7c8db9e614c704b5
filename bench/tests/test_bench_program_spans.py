"""The readers of the program's own spans (``repro.core.tracing``) on a
synthetic recorder, and the program's epochs counted against the
benchmark's wrappers in a rehearsal."""
import sys
import types

import pytest

from bench import drive, spec
from repro.core import tracing

READERS = ("view_ms_per_epoch", "upload_ms_per_epoch", "upload_mb_per_epoch",
           "readback_ms_per_epoch")


def _ctx(*windows):
    """A reader context whose wrapper spans cover ``windows``."""
    return types.SimpleNamespace(spans=types.SimpleNamespace(
        records=[("service.drain_epoch", lo, hi) for lo, hi in windows]))


def _span(rec, name, t0, t1, **attrs):
    rec._push(tracing.Record(next(rec._ids), 0, 0, name, t0, t1, attrs))


@pytest.fixture
def rec(monkeypatch):
    r = tracing.Recorder()
    monkeypatch.setattr(tracing, "RECORDER", r)
    # two epochs inside [10, 20], one outside it
    for base in (10.0, 15.0, 30.0):
        _span(r, "state.epoch_view", base + 0.1, base + 0.102)
        _span(r, "engine_jax.upload", base + 0.2, base + 0.25,
              **{"engine_jax.upload_bytes": 3_000_000})
        _span(r, "online.begin_epoch", base, base + 1.0)
        _span(r, "engine_jax.readback", base + 2.0, base + 2.004,
              **{"jax.lowerings": 1})
    return r


def _read(metric, ctx):
    return spec.reader(metric)(ctx)


def test_readers_divide_the_window_by_its_epochs(rec):
    ctx = _ctx((10.0, 12.0), (15.0, 20.0))
    assert _read("view_ms_per_epoch", ctx) == pytest.approx(2.0)
    assert _read("upload_ms_per_epoch", ctx) == pytest.approx(50.0)
    assert _read("upload_mb_per_epoch", ctx) == pytest.approx(3.0)
    assert _read("readback_ms_per_epoch", ctx) == pytest.approx(4.0)


def test_a_count_outside_any_span_is_read_in_its_window(rec):
    rec._push(tracing.Record(next(rec._ids), 0, 0, "engine_jax.upload_bytes",
                             16.0, 16.0, {"engine_jax.upload_bytes": 2e6}))
    assert _read("upload_mb_per_epoch", _ctx((10.0, 20.0))) == \
        pytest.approx(4.0)


@pytest.mark.parametrize("metric", READERS)
def test_a_window_without_an_epoch_reads_nothing(rec, metric):
    assert _read(metric, _ctx((20.5, 29.0))) is None
    assert _read(metric, _ctx()) is None


@pytest.mark.parametrize("metric", READERS)
def test_records_dropped_inside_the_window_read_nothing(monkeypatch, metric):
    r = tracing.Recorder(maxlen=4)
    monkeypatch.setattr(tracing, "RECORDER", r)
    for base in (10.0, 15.0):
        _span(r, "engine_jax.upload", base + 0.2, base + 0.25)
        _span(r, "engine_jax.readback", base + 2.0, base + 2.004)
        _span(r, "state.epoch_view", base + 0.1, base + 0.102)
        _span(r, "online.begin_epoch", base, base + 1.0)
    assert r.dropped == 4
    assert _read(metric, _ctx((10.0, 20.0))) is None
    # the drops all ended before this window opened
    assert _read(metric, _ctx((14.0, 20.0))) is not None


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_the_recorder_reads_nothing(monkeypatch, metric):
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert _read(metric, _ctx((10.0, 20.0))) is None


@pytest.mark.parametrize("cell", ("borg2011-rpsdsf.fill",
                                  "alibaba2018-drf-rrr.churn"))
def test_the_program_counts_the_epochs_the_wrappers_count(cell):
    c = spec.load_cell(cell, rehearse=True)
    service, log, plan = drive.set_up(c, 7, 1.0)
    spans = drive.Spans()
    drive.LOOPS[c.traffic["loop"]](c, service, log, plan, 7, 1.0,
                                   spans=spans)
    assert spans.count("online.begin_epoch") > 0
    lo = min(t0 for _, t0, _ in spans.records)
    hi = max(t1 for _, _, t1 in spans.records)
    names = [r.name for r in tracing.records(lo, hi)]
    for name, *_ in drive.SPAN_POINTS:
        assert names.count(name) == spans.count(name), name
