"""tick_ms: the window's length over the compiled hybrid steps each shard
dispatched in it (``TopoServingEngine.total_steps``, its change across the
window, divided by the number of shards)."""


def read(ctx):
    if ctx.steps <= 0:
        return None
    return 1e3 * ctx.seconds / (ctx.steps / ctx.shards)
