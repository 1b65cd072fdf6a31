"""Share (%) of the key slots sent to the device that are padding:
1 - real keys / slots, summed over every ``align.dispatch`` span (each
carries ``keys``, the real keys of both sides of its pairs, and
``slots``, 2 x padded rows x P of its batch).  A count, not a time."""


def read(ctx):
    spans = [s for s in ctx.spans
             if s.name == "align.dispatch" and "slots" in s.attrs]
    slots = sum(s.attrs["slots"] for s in spans)
    if not slots:
        return None
    return 100.0 * (1.0 - sum(s.attrs["keys"] for s in spans) / slots)
