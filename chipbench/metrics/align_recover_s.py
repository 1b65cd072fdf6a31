"""Seconds per alignment recovering the rounds' outputs on the host once
each dispatch has returned: device-to-host copies, the 64-bit join,
per-pair selection and sort (``align.recover`` spans)."""


def read(ctx):
    if not ctx.span_count("align.recover"):
        return None
    return ctx.per_job(ctx.span_seconds("align.recover"))
