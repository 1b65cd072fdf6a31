"""Seconds per pipeline job that JAX spent making programs inside the
window (tracing, lowering, compiling or loading from the persistent
cache), from ``jax.monitoring``."""


def read(ctx):
    if not ctx.span_count("pipeline.run"):
        return None
    return ctx.per_job(ctx.compile_s)
