"""step_mfu: CRONet forward operations of the window's completed,
forward-bearing iterations (each iteration past the ``hist_len`` warm-up
runs the forward for its request) per second, over the chips' bf16 peak
(``bench/peaks.json``). Model work only: the CG's work is not counted."""


def read(ctx):
    c = ctx.cfg
    dims = dict(c["cronet"], nelx=c["nelx"], nely=c["nely"])
    hist = c["cronet"]["hist_len"]
    fwd = sum(max(0, r["n_iter"] - hist) for r in ctx.records)
    if fwd == 0:
        return None
    peak = ctx.flops.peak(ctx.peaks, ctx.device_kind, "bf16_flops_per_s")
    rate = ctx.flops.forward_flops(dims) * fwd / ctx.window_s
    return 100.0 * rate / (ctx.chips * peak)
