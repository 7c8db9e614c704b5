"""On-chip benchmark of the served allocator (``python3 bench/run.py``).

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     deployment: machines, scheduler, guarantees
    bench/traffic/<traffic>.json    traffic mix parameters (one generator)
    bench/cells/<cell>.json         optional per-cell parameters (e.g. a rate)
    bench/metrics/<metric>.py       reader of one per-layer metric
"""
