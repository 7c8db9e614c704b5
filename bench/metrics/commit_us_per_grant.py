"""Microseconds of ``OnlineAllocator.commit_epoch`` per grant, less the
wait for the device result inside it: the float64 revalidation and the
``_grant`` commit of each grant."""


def read(ctx):
    grants = ctx.outcome.grants
    if not grants or not ctx.spans.count("online.commit_epoch"):
        return None
    own = (ctx.spans.total("online.commit_epoch")
           - ctx.spans.total("engine_jax.result"))
    return 1e6 * own / grants
