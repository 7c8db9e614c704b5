"""Device microseconds of the epoch programs in the traced window, per
grant the window committed."""


def read(ctx):
    dev = ctx.epoch_device_s()
    if dev is None or not ctx.outcome.grants:
        return None
    return 1e6 * dev / ctx.outcome.grants
