"""Percent of the traced window in which no operation ran on the device."""


def read(ctx):
    busy, window = ctx.busy_s(), ctx.window_s()
    if busy is None or not window:
        return None
    return 100.0 * (1.0 - busy / window)
