"""Microseconds of ``AllocatorService.complete`` per executor released."""


def read(ctx):
    return ctx.per("service.complete", ctx.outcome.released_executors, 1e6)
