"""Seconds per job lowering and compiling the coreset's batched k-means
program, which ``cluster_coreset`` builds on every call
(``coreset.compile`` spans)."""


def read(ctx):
    if not ctx.span_count("coreset.compile"):
        return None
    return ctx.per_job(ctx.span_seconds("coreset.compile"))
