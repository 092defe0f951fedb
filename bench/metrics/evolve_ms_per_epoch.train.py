"""Device time of SET evolution (the traffic's ``programs.evolve`` XLA
module) per traced epoch, averaged over the chips."""


def read(ctx):
    secs = ctx.module_seconds(ctx.traffic["programs"]["evolve"])
    if not secs or not ctx.traced_epochs:
        return None
    return 1e3 * secs / ctx.traced_epochs
