"""Milliseconds of ``EpochCache.fingerprint`` per epoch."""


def read(ctx):
    return ctx.per("epoch_cache.fingerprint",
                   ctx.spans.count("online.begin_epoch"), 1e3)
