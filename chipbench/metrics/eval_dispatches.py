"""Device dispatches per job scoring the test rows: ``serve.dispatch``
spans (one put, one program and one read each) over the jobs that ran
``pipeline.serve``.  A count, not a time."""


def read(ctx):
    if not ctx.span_count("pipeline.serve"):
        return None
    return ctx.per_job(ctx.span_count("serve.dispatch"))
