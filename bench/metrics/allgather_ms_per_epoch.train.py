"""Device time of the all-gather ops on device 0 per traced epoch; the part
of it during which no other op ran (exposed) is printed on an earlier
line."""


def read(ctx):
    if ctx.red is None or 0 not in ctx.red["devices"] or not ctx.traced_epochs:
        return None
    d = ctx.red["devices"][0]
    if not d["collective_ns"]:
        return None
    ctx.note(f"allgather_ms_per_epoch.train: exposed "
             f"{d['collective_exposed_ns'] / 1e6 / ctx.traced_epochs!r} ms per epoch")
    return d["collective_ns"] / 1e6 / ctx.traced_epochs
