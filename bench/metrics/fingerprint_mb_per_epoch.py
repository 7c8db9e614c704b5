"""Megabytes (10^6 bytes) handed to blake2b per epoch by the epoch-cache
fingerprint (the program's ``epoch_cache.hashed_bytes`` counter).  Nothing
where the program does not count them."""

from bench.metrics import _program

COUNTER = "epoch_cache.hashed_bytes"


def read(ctx):
    got = _program.in_window(ctx)
    if got is None or not any(COUNTER in r.attrs for r in got[0]):
        return None
    return _program.per_epoch(ctx, COUNTER, 1e-6)
