"""Milliseconds per epoch of reading the grant sequence back once the
device has run the epoch: slice, transfer, conversion to host lists, and
any program compiled for them (the program's ``engine_jax.readback``
spans)."""

from bench.metrics import _program


def read(ctx):
    return _program.ms_per_epoch(ctx, "engine_jax.readback")
