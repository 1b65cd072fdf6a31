"""Seconds per job preparing training on the host before the warm-up
epoch: program lookup, parameter init, slab packing and uploads
(``train.setup`` spans)."""


def read(ctx):
    if not ctx.span_count("train.setup"):
        return None
    return ctx.per_job(ctx.span_seconds("train.setup"))
