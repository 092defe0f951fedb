"""Device time of the step program (the traffic's ``programs.step`` XLA
module: the fused epoch segment, or WASAP's phase-1 epoch) per step it ran,
averaged over the chips: for WASAP per worker-step of one chip."""


def read(ctx):
    secs = ctx.module_seconds(ctx.traffic["programs"]["step"])
    if not secs or not ctx.traced_epochs:
        return None
    return 1e3 * secs / (ctx.traced_epochs * ctx.worker_steps_per_chip)
