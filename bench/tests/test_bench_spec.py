"""A cell, configuration, mix and per-layer metric are found from added
files alone."""
import json
import types

import pytest

from bench import spec


def _write(root, rel, text):
    path = root / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def test_a_cell_is_discovered_from_added_files(tmp_path):
    bench = {
        "configs": [{"name": "tiny", "source": "x", "why": "x",
                     "file": "bench/configs/tiny.json", "reduced": []}],
        "workloads": [{"name": "tiny.burst", "config": "tiny",
                       "traffic": "burst", "chips": 1, "why": "x"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "other_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock", "workloads": ["nope"]}],
        "per_layer": [
            {"name": "spans_seen", "unit": "1", "better": "higher",
             "source": "program_span", "layer": "x", "moves": "setup_s",
             "workloads": ["tiny.burst"]}],
    }
    _write(tmp_path, "BENCHMARK.json", json.dumps(bench))
    _write(tmp_path, "bench/configs/tiny.json", json.dumps(
        {"machines": [], "size": 1, "criterion": "drf",
         "server_policy": "rrr", "rehearse": {"size": 0}}))
    _write(tmp_path, "bench/traffic/burst.json", json.dumps(
        {"loop": "rounds", "batch": 4, "rehearse": {"batch": 2}}))
    _write(tmp_path, "bench/cells/tiny.burst.json", json.dumps(
        {"rate_rps": 9, "rehearse": {"rate_rps": 3}}))
    _write(tmp_path, "bench/metrics/spans_seen.py",
           "def read(ctx):\n    return ctx.spans.count('x') or None\n")

    cell = spec.load_cell("tiny.burst", root=str(tmp_path))
    assert cell.config["size"] == 1 and cell.chips == 1
    assert cell.traffic == {"loop": "rounds", "batch": 4, "rate_rps": 9}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["spans_seen"]

    small = spec.load_cell("tiny.burst", root=str(tmp_path), rehearse=True)
    assert small.config["size"] == 0
    assert small.traffic == {"loop": "rounds", "batch": 2, "rate_rps": 3}

    read = spec.reader("spans_seen", root=str(tmp_path))
    ctx = types.SimpleNamespace(spans=types.SimpleNamespace(
        count=lambda name: 5))
    assert read(ctx) == 5


def test_a_split_metric_falls_back_to_the_reader_of_its_quantity(tmp_path):
    _write(tmp_path, "bench/metrics/spans_seen.py",
           "def read(ctx):\n    return 7\n")
    assert spec.reader("spans_seen.churn", root=str(tmp_path))(None) == 7
    with pytest.raises(spec.SpecError):
        spec.reader("other.churn", root=str(tmp_path))


def test_unknown_cell_and_reader_are_errors(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_cell("no.such.cell")
    with pytest.raises(spec.SpecError):
        spec.reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.reader("../escape")


def test_every_metric_of_the_benchmark_has_a_reader():
    with open(f"{spec.ROOT}/BENCHMARK.json") as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        assert callable(spec.reader(m["name"]))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.per_layer and cell.end_to_end
