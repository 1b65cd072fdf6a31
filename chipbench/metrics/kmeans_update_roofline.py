"""Share (%) of the roofline reached by the fused Lloyd update kernels
(``kmeans_update`` and its gather form): their device time in the trace
against the work of every update the jobs' k-means fits need
(``work/kmeans_update``, from real rows, features and clusters)."""
from chipbench.work import kmeans_update


def read(ctx):
    secs = ctx.trace.kernel_seconds(["kmeans_update"], prefix=True)
    ops = nbytes = 0
    for j in ctx.jobs:
        km = j.get("kmeans")
        if not km:
            continue
        for d in j["widths"]:
            o, b = kmeans_update.count(j["n_align"], d, km["k"])
            ops += km["iters"] * o
            nbytes += km["iters"] * b
    return ctx.roofline(ops, nbytes, secs)
