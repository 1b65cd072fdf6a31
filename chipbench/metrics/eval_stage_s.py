"""Seconds per job scoring the test rows (``pipeline.serve`` span, which
brackets ``evaluate()`` through the serving score path)."""


def read(ctx):
    if not ctx.span_count("pipeline.serve"):
        return None
    return ctx.per_job(ctx.span_seconds("pipeline.serve"))
