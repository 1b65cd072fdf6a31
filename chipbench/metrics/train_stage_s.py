"""Seconds per job in the training stage (``pipeline.train`` span)."""


def read(ctx):
    if not ctx.span_count("pipeline.train"):
        return None
    return ctx.per_job(ctx.span_seconds("pipeline.train"))
