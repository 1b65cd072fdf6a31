"""Share (%) of the roofline reached by the split model's bottom-layer
kernels (every ``splitnn_bottom*`` kernel): their device time against
the bottom forward of every row trained (``epochs * n_train``; the
warm-up epoch is not work the job needs) and every test row scored."""
from chipbench.work import splitnn_bottom


def read(ctx):
    secs = ctx.trace.kernel_seconds(["splitnn_bottom"], prefix=True)
    ops = nbytes = 0
    for j in ctx.jobs:
        rows = j["epochs"] * j["n_train"] + j["n_test"]
        o, b = splitnn_bottom.count(rows, j["widths"], j["bottom"])
        ops += o
        nbytes += b
    return ctx.roofline(ops, nbytes, secs)
