"""Share (%) of the roofline reached by the sorted-merge intersection
kernels (``sorted_intersect_merge`` and, on the tiled path,
``sorted_intersect_cross``): their device time against the merge work
of every pair of every round (``work/sorted_intersect``, from the real
keys of both sides).  The merge's comparisons run on the vector unit,
whose peak the published table does not give; at 16 bytes moved per
comparison the merge is bound by HBM on any unit, so its least time is
its bytes over the HBM bandwidth."""
from chipbench.work import sorted_intersect


def read(ctx):
    secs = ctx.trace.kernel_seconds(["sorted_intersect"], prefix=True)
    nbytes = 0
    for j in ctx.jobs:
        for n in j.get("merge_keys", []):
            nbytes += sorted_intersect.count(n)[1]
    return ctx.hbm_roofline(nbytes, secs)
