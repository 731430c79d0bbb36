"""latency_p95_s: 95th percentile of the same set as latency_p50_s."""
import numpy as np


def read(ctx):
    if ctx.mix["loop"] != "open" or not ctx.records:
        return None
    return float(np.percentile([r["latency_s"] for r in ctx.records], 95))
