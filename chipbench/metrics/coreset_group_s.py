"""Seconds per job grouping the aligned rows by (CT, label) and keeping
each group's row of least summed distance, at the label owner
(``coreset.group`` spans, inside ``coreset.select``)."""


def read(ctx):
    if not ctx.span_count("coreset.group"):
        return None
    return ctx.per_job(ctx.span_seconds("coreset.group"))
