"""Seconds per job in the Cluster-Coreset stage (``pipeline.coreset``)."""


def read(ctx):
    if not ctx.span_count("pipeline.coreset"):
        return None
    return ctx.per_job(ctx.span_seconds("pipeline.coreset"))
