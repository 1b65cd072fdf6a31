"""Seconds per alignment outside the device dispatches: id
canonicalisation, lane packing, id recovery and the HE broadcast on the
host (a whole job's time less its ``align.dispatch`` spans)."""


def read(ctx):
    if ctx.span_count("pipeline.run") or not ctx.span_count("align.dispatch"):
        return None
    return ctx.per_job(ctx.span_seconds("bench.job")
                       - ctx.span_seconds("align.dispatch"))
