"""Milliseconds of ``OnlineAllocator.begin_epoch`` per epoch: admission
gate, preemption pass, epoch view, fingerprint, host staging and upload of
the device epoch."""


def read(ctx):
    return ctx.per("online.begin_epoch",
                   ctx.spans.count("online.begin_epoch"), 1e3)
