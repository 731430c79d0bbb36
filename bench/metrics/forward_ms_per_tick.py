"""forward_ms_per_tick: device time of the hybrid step's CRONet forward
per tick of a shard, from the profiler trace of the middle of the window:
the ``cronet_fused`` kernel's ops in ``bench/trace_reduce.py``'s
breakdown, summed over the cell's chips, in ms, over the ticks the span
holds at the window's rate (``bench/span_reduce.py``'s ``ms_per_tick``)."""
from bench import span_reduce


def read(ctx):
    return span_reduce.ms_per_tick(ctx, "cronet_forward")
