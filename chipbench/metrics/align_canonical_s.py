"""Seconds per alignment putting every party's ids in canonical form
(sorted, unique) before the first round (``align.canonical`` spans)."""


def read(ctx):
    if not ctx.span_count("align.canonical"):
        return None
    return ctx.per_job(ctx.span_seconds("align.canonical"))
