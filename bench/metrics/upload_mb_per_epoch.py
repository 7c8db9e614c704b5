"""Megabytes (10^6 bytes) handed to the device per epoch, at the device's
dtypes (the program's ``engine_jax.upload_bytes`` counter)."""

from bench.metrics import _program


def read(ctx):
    return _program.per_epoch(ctx, "engine_jax.upload_bytes", 1e-6)
