#!/usr/bin/env python3
"""Benchmark one cell of the served allocator on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's cluster and load from the seed, warms every epoch program
the window will run, measures the window from the client's side, then
replays the window's epochs against the configuration's plain reference
(``bench/reference.py``, or its own under ``bench/references/``) to decide
``correct``.  With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` a profiler trace of the window and spans around
the program's layer entry points give its per-layer metrics and a
breakdown.  The last line of standard output is the result object; the
numbers compared are the last lines of standard error.

A process that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.  ``--rehearse`` runs the cell at the small
sizes its files give, on the CPU, for tests.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, "bench", ".jax_cache")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="small sizes on the CPU backend (tests only)")
    return ap.parse_args(argv)


def _paths() -> None:
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def device_info(chips: int, rehearse: bool) -> dict:
    import jax

    devs = jax.devices()
    if not rehearse and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_peak(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def _pct(values, q) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, float), q))


def end_to_end(out, setup_s: float, give_up_ms: float) -> dict:
    """Every end-to-end metric the loop can give (the cell keeps its own)."""
    vals = {"setup_s": setup_s,
            "decisions_per_s": out.grants / out.window_s}
    if out.latencies_ms is not None and out.attempted:
        # a request never granted sits above every limit: it counts with
        # the wait until the run gave up on it
        lat = list(out.latencies_ms) + [give_up_ms] * out.failed
        vals["decision_p50_ms"] = _pct(lat, 50)
        vals["decision_p99_ms"] = _pct(lat, 99)
    return vals


def run_cell(args, *, root: str = ROOT, control: bool = False) -> dict:
    """One run of a cell; returns the result object (raises NoChip).
    ``control`` also replays the window with the configuration's control
    in the program's place (``bench/control.py``), under ``"control"``."""
    _paths()
    import repro.launch.alloc_serve  # noqa: F401  (the program, before JAX)
    from bench import spec

    cell = spec.load_cell(args.workload, root=root, rehearse=args.rehearse)
    import jax

    if not args.rehearse:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    device = device_info(cell.chips, args.rehearse)
    from bench import context, drive, ledger, trace_reduce, warm

    service, log, plan = drive.set_up(cell, args.seed, args.seconds)
    n_before = len(service.alloc.frameworks)
    warmed = warm.warm(cell, service, plan, args.seed)
    warm_grants = drive.warm_up(cell, service, log, plan, args.seed)
    loop = drive.LOOPS[cell.traffic["loop"]]
    spans = drive.Spans() if args.trace else None
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        setup_s = time.perf_counter() - _T_START
        out = loop(cell, service, log, plan, args.seed, args.seconds,
                   spans=spans,
                   annotate=(lambda: jax.profiler.TraceAnnotation(
                       trace_reduce.WINDOW_SPAN)) if trace_dir else None)
        trace = None
        if trace_dir:
            jax.profiler.stop_trace()
            trace = trace_reduce.load(
                trace_dir, [p[0] for p in drive.SPAN_POINTS])
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = _memory_peak(cell.chips)
    program_free = service.alloc.free
    del service
    t_check = time.perf_counter()
    verdict = ledger.check(log, cell.config, cell.reference, program_free)
    verdict["check_s"] = time.perf_counter() - t_check
    checks = verdict["checks"]
    ctl = (ledger.check(log, cell.config, cell.reference, program_free,
                        control=cell.config["control"]["kind"])
           if control else None)
    correct = (verdict["epochs_compared"] > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed}
    if args.trace:
        ctx = context.Context(spans, out, trace,
                              len(cell.config["resources"]), device["kind"])
        values = {}
        for m in cell.per_layer:
            v = spec.reader(m["name"], root=root)(ctx)
            if v is not None:
                values[m["name"]] = v
        if ctx.has_device():
            device["busy_s"] = ctx.busy_s()
            device["window_s"] = ctx.window_s()
            lo, hi = trace_reduce.window(trace)
            result["breakdown"] = {
                "device_ops": trace_reduce.top_ops(trace, lo, hi),
                "idle_gaps": trace_reduce.idle_gaps(trace, lo, hi)}
    else:
        grace_ms = 1e3 * (out.window_s + float(cell.traffic.get("grace_s", 0)))
        vals = end_to_end(out, setup_s, grace_ms)
        values = {m["name"]: vals[m["name"]] for m in cell.end_to_end
                  if m["name"] in vals}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in values.items()}
    result["device"] = device
    if ctl is not None:
        result["control"] = {"kind": cell.config["control"]["kind"],
                             "first_difference": ctl["first_difference"],
                             "checks": ctl["checks"]}
    result["checks"] = checks

    _report(cell, out, verdict, warmed, setup_s, n_before, warm_grants)
    return result


def _report(cell, out, verdict, warmed, setup_s, n_before,
            warm_grants) -> None:
    """The earlier lines: counts, counters and how late the generator ran."""
    c = out.counters
    lines = [
        f"cell {cell.name} window_s {out.window_s} epochs {out.epochs} "
        f"grants {out.grants} attempted {out.attempted} failed {out.failed}",
        f"setup_s {setup_s} frameworks_at_start {n_before} "
        f"warm_dispatches {warmed} warm_up_grants {warm_grants}",
        f"compiles_in_window {c['traces']} dispatches {c['dispatches']} "
        f"cache_hits {c['cache_hits']} cache_misses {c['cache_misses']} "
        f"faults {c['faults_nonzero'] or 'none'}",
        f"occupancy_dominant {out.occupancy} "
        f"released_executors {out.released_executors}",
        f"epochs_compared {verdict['epochs_compared']} "
        f"check_s {verdict['check_s']} "
        f"first_difference {verdict['first_difference']}",
    ]
    if out.rounds_s:
        lines.append(f"rounds_s {' '.join(map(str, out.rounds_s))}")
    if out.latencies_ms is not None:
        lines.append(f"latency_samples {len(out.latencies_ms)} "
                     f"failed_samples {out.failed}")
    if out.lateness_ms:
        lines.append(f"generator_late_ms p99 {_pct(out.lateness_ms, 99)} "
                     f"max {max(out.lateness_ms)}")
    for line in lines:
        print(line, flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run_cell(args)
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    except ImportError as exc:
        print(f"bench: the program is not importable here: {exc}",
              file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
