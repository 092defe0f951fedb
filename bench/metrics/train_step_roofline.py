"""Least time of one training step over its measured device time: the
least time is the larger of the step's FLOPs over the FLOP peak and its
least bytes over the memory bandwidth (``bench/work.py``), the measured
time the step program's device time per step."""
from bench import work


def read(ctx):
    secs = ctx.module_seconds(ctx.traffic["programs"]["step"])
    if not secs or not ctx.traced_epochs or ctx.peak is None:
        return None
    step_s = secs / (ctx.traced_epochs * ctx.worker_steps_per_chip)
    cfg = ctx.config
    least, bound = work.least_step_seconds(
        cfg["layer_dims"], cfg["nnz"], cfg["batch_size"], ctx.peak)
    ctx.note(f"train_step_roofline: least {least * 1e6:.3f} us per step, "
             f"{bound}-bound; measured {step_s * 1e6:.3f} us")
    return 100.0 * least / step_s
