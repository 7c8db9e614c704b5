"""Milliseconds of host staging per epoch: pad, cast and transfer of the
epoch's inputs and the launch of each dispatch (the program's
``engine_jax.upload`` spans)."""

from bench.metrics import _program


def read(ctx):
    return _program.ms_per_epoch(ctx, "engine_jax.upload")
