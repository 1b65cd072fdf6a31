"""Seconds per alignment inside the engine's device dispatches (the
``align.dispatch`` spans, each ending in ``block_until_ready``)."""


def read(ctx):
    if ctx.span_count("pipeline.run") or not ctx.span_count("align.dispatch"):
        return None
    return ctx.per_job(ctx.span_seconds("align.dispatch"))
