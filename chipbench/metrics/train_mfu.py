"""Share (%) of the chip's peak that training reached: the split model's
training flops per row (``work/splitnn_model``) times the rows trained
per second inside the ``train.epoch`` spans (the warm-up epoch under
``train.compile`` is not counted), over the peak of the configuration's
dtype."""
from chipbench.work import splitnn_model


def read(ctx):
    secs = ctx.span_seconds("train.epoch")
    if secs <= 0 or not ctx.jobs:
        return None
    flops = sum(j["epochs"] * j["n_train"] * splitnn_model.train_flops(
        j["widths"], j["bottom"], j["hidden"], j["n_out"]) for j in ctx.jobs)
    return 100.0 * flops / secs / ctx.ops_peak()
