"""cg_iters_per_solve: CG iterations per FEA fallback, over the window's
completions (sum of ``cg_iters`` over sum of ``fea_iters``). A count."""


def read(ctx):
    fea = sum(r["fea_iters"] for r in ctx.records)
    if fea == 0:
        return None
    return sum(r["cg_iters"] for r in ctx.records) / fea
