"""Share (%) of the traced window of pipeline jobs in which no operation
ran on the device: 1 - busy / window, busy being the union of the
device's op intervals."""


def read(ctx):
    if not ctx.span_count("pipeline.run") or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
