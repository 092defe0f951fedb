"""Model FLOPs utilisation of the whole job: forward + backward FLOPs per
sample (``bench/work.py``) times the samples trained per second over the
traced window (device time and host time between epochs alike), over the
chips' bf16 peak."""
from bench import work


def read(ctx):
    if ctx.red is None or not ctx.traced_epochs or ctx.peak is None:
        return None
    rate = ctx.traced_epochs * ctx.samples_per_epoch / (ctx.red["window_ns"] / 1e9)
    flops = work.flops_per_sample(ctx.config["nnz"]) * rate
    return 100.0 * flops / (ctx.chips * ctx.peak["flops_per_s"])
