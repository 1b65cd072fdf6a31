"""Seconds per job in the alignment stage (``pipeline.align`` span)."""


def read(ctx):
    if not ctx.span_count("pipeline.align"):
        return None
    return ctx.per_job(ctx.span_seconds("pipeline.align"))
