"""Seconds per alignment packing the rounds' batches on the host: id or
key lanes padded to the round's P, seeds and filler rows, up to each
device dispatch (``align.pack`` spans)."""


def read(ctx):
    if not ctx.span_count("align.pack"):
        return None
    return ctx.per_job(ctx.span_seconds("align.pack"))
