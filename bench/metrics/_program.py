"""What the readers of the program's own spans (``repro.core.tracing``)
share: the program's records inside the traced window, and the epochs
they cover.  The window is that of the benchmark's wrapper spans, which
are on the same clock (``time.perf_counter``)."""


def in_window(ctx):
    """``(records, epochs)``: the program's records inside the window and
    the number of its ``online.begin_epoch`` spans there.  None where the
    program has no recorder, the window holds no epoch, or records that
    ended inside the window were dropped from the recorder's ring."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    spans = ctx.spans.records
    if not spans:
        return None
    lo = min(t0 for _, t0, _ in spans)
    hi = max(t1 for _, _, t1 in spans)
    if tracing.lost_since(lo):
        return None
    recs = tracing.records(lo, hi)
    epochs = sum(1 for r in recs if r.name == "online.begin_epoch")
    return (recs, epochs) if epochs else None


def ms_per_epoch(ctx, name: str):
    """Milliseconds of the program's spans ``name`` per epoch."""
    got = in_window(ctx)
    if got is None:
        return None
    recs, epochs = got
    return 1e3 * sum(r.t1 - r.t0 for r in recs if r.name == name) / epochs


def per_epoch(ctx, counter: str, scale: float):
    """``scale`` x the program's counter ``counter`` per epoch."""
    got = in_window(ctx)
    if got is None:
        return None
    recs, epochs = got
    return scale * sum(r.attrs.get(counter, 0) for r in recs) / epochs
