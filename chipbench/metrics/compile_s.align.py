"""Seconds per alignment that JAX spent making programs inside the
window, from ``jax.monitoring``."""


def read(ctx):
    if ctx.span_count("pipeline.run") or not ctx.span_count("align.round"):
        return None
    return ctx.per_job(ctx.compile_s)
