"""latency_p50_s: median, over every request due in the window, of the
time from when it was due to be sent to its completion."""
import numpy as np


def read(ctx):
    if ctx.mix["loop"] != "open" or not ctx.records:
        return None
    return float(np.percentile([r["latency_s"] for r in ctx.records], 50))
