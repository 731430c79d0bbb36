"""device_idle_frac: 1 - (union of the device's operation intervals over
the traced span), from the profiler trace of the middle of the window;
the mean over the chips the cell uses."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["busy_s"]:
        return None
    idle = [1.0 - b / ctx.trace["window_s"] for b in ctx.trace["busy_s"].values()]
    return sum(idle) / len(idle)
