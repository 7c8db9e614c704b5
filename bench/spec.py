"""Find a cell's configuration, traffic mix, reference and metric readers
by name.

Nothing here names a cell, configuration, mix, criterion, server policy or
metric: a later change adds one by adding its files and its entry in
``BENCHMARK.json``.  A configuration brings its reference as a file of its
own (:func:`reference`), its framework weights as a table in its file
(``bench/traffic.py``), and settings of the service as its ``service``
object (keyword arguments of ``AllocatorService``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import inspect
import json
import os
import re

from bench import drive
from bench import reference as plain

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class SpecError(ValueError):
    """A cell, configuration, mix or reader that cannot be found or read."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file, rehearse overrides applied
    traffic: dict          # the mix, then the cell's own parameters
    end_to_end: list       # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    reference: object      # the configuration's epoch(config, **inputs)


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path)}") from None


def _checked(name: str) -> str:
    if not _NAME.match(name):
        raise SpecError(f"not a valid name: {name!r}")
    return name


def _overlay(base: dict, rehearse: bool) -> dict:
    out = {k: v for k, v in base.items() if k != "rehearse"}
    if rehearse:
        out.update(base.get("rehearse", {}))
    return out


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, *, root: str = ROOT, rehearse: bool = False) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files read."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no cell {name!r} in BENCHMARK.json "
                        f"(cells: {', '.join(sorted(cells))})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf_entry = configs[w["config"]]
    config = _overlay(_read_json(os.path.join(root, conf_entry["file"])),
                      rehearse)
    epoch = reference(config, root=root)
    _check_service(config)
    traffic = _overlay(_read_json(os.path.join(
        root, "bench", "traffic", _checked(w["traffic"]) + ".json")), rehearse)
    cell_file = os.path.join(root, "bench", "cells", _checked(name) + ".json")
    if os.path.exists(cell_file):
        traffic.update(_overlay(_read_json(cell_file), rehearse))
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        reference=epoch)


def _load(path: str, prefix: str, name: str):
    spec = importlib.util.spec_from_file_location(
        prefix + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config: dict, *, root: str = ROOT):
    """The ``epoch`` function of the configuration's plain reference:
    ``bench/references/<criterion>-<server_policy>.py`` where that file
    exists, else ``bench/reference.py`` for the pairs it covers (its
    docstring gives a reference file's contract)."""
    try:
        pair = (_checked(config["criterion"]),
                _checked(config["server_policy"]))
    except KeyError as exc:
        raise SpecError(f"the configuration names no {exc.args[0]}") from None
    rel = os.path.join("bench", "references", "-".join(pair) + ".py")
    path = os.path.join(root, rel)
    if os.path.exists(path):
        return _load(path, "bench_reference_", "-".join(pair)).epoch
    if pair in plain.COVERED:
        return plain.config_epoch
    raise SpecError(f"no reference for {'/'.join(pair)}: add {rel}")


def _check_service(config: dict) -> None:
    """Refuse a key of the configuration's ``service`` object that
    ``AllocatorService`` does not take, or that the harness sets."""
    if "service" not in config:
        return
    from repro.launch.alloc_serve import AllocatorService

    takes = set(inspect.signature(AllocatorService).parameters)
    bad = sorted(set(config["service"]) - (takes - set(drive.SERVICE_SET)))
    if bad:
        raise SpecError(f"AllocatorService takes no service setting "
                        f"{', '.join(map(repr, bad))} from a configuration")


def reader(metric: str, *, root: str = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``.  A
    metric split by the end-to-end metric it moves (``<quantity>.<suffix>``)
    falls back to the reader of its quantity, ``<quantity>.py``."""
    base = os.path.join(root, "bench", "metrics")
    path = os.path.join(base, _checked(metric) + ".py")
    if not os.path.exists(path) and "." in metric:
        path = os.path.join(base, metric.rsplit(".", 1)[0] + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no reader for metric {metric!r} under "
                        f"{os.path.relpath(base, root)}")
    return _load(path, "bench_metric_", metric).read
