"""Share of the traced window in which no operation ran on device 0:
1 - (union of its op intervals) / (window between the epoch markers)."""


def read(ctx):
    if ctx.red is None or 0 not in ctx.red["devices"]:
        return None
    busy = ctx.red["devices"][0]["busy_ns"]
    return 100.0 * (1.0 - busy / ctx.red["window_ns"])
