"""queue_wait_p95_s: 95th percentile of ``TopoRequest.queue_wait_s``
(gateway submit to first slot admission) over the window's requests."""
import numpy as np


def read(ctx):
    if not ctx.records:
        return None
    return float(np.percentile([r["queue_wait_s"] for r in ctx.records], 95))
