"""Decides ``correct``: the served requests against the plain reference.

After the window has closed and the system's state is freed, a sample of
the requests that completed in the window, drawn from the seed and
holding the longest request, is replayed with the plain reference and its
own weights, made from the same seed by the benchmark's generator. The
tap (``bench/tap.py``) keeps, for each request, the last 12 designs the
system produced (``x[n-11] .. x[n]``, the last one served) and the
residual gate's last error. The reference replays each of the 11 SIMP
iterations between them from the system's input design, and the last
one with the surrogate too. Three numbers are compared, one per layer:

- ``err_gap``: the surrogate's relative error at the last iteration (the
  residual gate's input) against the reference's, as a share of the
  reference's. It covers the CRONet forward;
- ``compliance_gap``: the served compliance (that of the last
  iteration's design) against the reference's FEA compliance of the same
  design, as a share. It covers the CG fallback;
- ``density_gap``: each of the 11 designs against the reference's filter
  and optimality-criteria update of the design before it, largest
  absolute difference of one element. It covers filter + OC, and through
  the sensitivities the CG of every replayed iteration.

Each has its limit in the configuration file (``limits``); a number that
is not finite fails. ``run(..., variants=True)`` also reads the same
numbers for the correctness control and for faults planted in what was
served (``bench/control.py``); the benchmark's own runs do not.
"""
from __future__ import annotations

import numpy as np

BLOCK = 8    # last iterations replayed together
ROWS = 32    # earlier iterations replayed together
NAMES = ("err_gap", "compliance_gap", "density_gap")


def sample(records: list, k: int, seed: int) -> list:
    """``k`` records drawn from the seed, always holding the one with the
    most iterations."""
    if len(records) <= k:
        return list(records)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    longest = max(range(len(records)), key=lambda i: records[i]["n_iter"])
    rest = [i for i in range(len(records)) if i != longest]
    pick = [longest] + list(rng.choice(rest, k - 1, replace=False))
    return [records[i] for i in sorted(pick)]


def _blocked(fn, arrays, size):
    """``fn`` over the rows of ``arrays`` in blocks of a fixed ``size``."""
    import jax
    import jax.numpy as jnp

    n = len(arrays[0])
    outs = []
    for s in range(0, n, size):
        idx = np.arange(s, s + size) % n
        outs.append(jax.device_get(fn(*(jnp.asarray(a[idx]) for a in arrays))))
    return [np.concatenate([o[i] for o in outs])[:n]
            for i in range(len(outs[0]))]


def replay(cfg: dict, ref, seed: int, inp: dict, dtype, filter_dtype=None):
    """The reference's answers from the system's inputs, computed in
    ``dtype`` (``filter_dtype``, where given, for filter + OC alone).
    Returns dict(err, compliance, designs (n, 11, nely, nelx), cg_iters,
    cg_broke) of the last iteration (designs: every replayed one)."""
    import jax
    import jax.numpy as jnp

    dims = dict(cfg["cronet"], nelx=cfg["nelx"], nely=cfg["nely"])
    n, t = inp["inputs"].shape[:2]

    def cast(a, dt=dtype):
        return jnp.asarray(a).astype(dt)

    # the SIMP constants enter every program as arguments, not as
    # constants: a constant exponent 3 is compiled as x*x*x, which rounds
    # otherwise than the program's x ** penal
    consts = (cast(ref.PENAL), cast(ref.E_MIN), cast(cfg["volfrac"]))

    def fea(consts, f, free, x):
        penal, e_min, volfrac = consts
        vf = jnp.full((len(x),), volfrac)
        if filter_dtype is None:
            return ref.fea_step(penal, e_min, vf, f, free, x)[1:]
        u, comp, _, its, broke = ref.fea_step(penal, e_min, vf, f, free, x)
        _, dc = ref.compliance_and_sens(x, u, penal, e_min)
        xl, dcl = cast(x, filter_dtype), cast(dc, filter_dtype)
        x_next = ref.oc_update(xl, ref.sensitivity_filter(xl, dcl),
                               cast(vf, filter_dtype))
        return comp, x_next.astype(dtype), its, broke

    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(cast, ref.make_params(dims, seed))

        @jax.jit
        def last(params, consts, f, free, fixed, hist, x_prev):
            f, free, fixed, hist, x_prev = map(cast, (f, free, fixed, hist,
                                                      x_prev))
            penal, e_min, volfrac = consts
            err, comp, x_next, its, broke = ref.last_step(
                dims, params, cfg["u_scale"], penal, e_min,
                jnp.full((len(f),), volfrac), f, free, fixed, hist, x_prev)
            if filter_dtype is not None:
                comp, x_next, its, broke = fea(consts, f, free, x_prev)
            return (err.astype(jnp.float32), comp.astype(jnp.float32),
                    x_next.astype(jnp.float32), its, broke)

        @jax.jit
        def earlier(consts, f, free, x):
            _, x_next, _, _ = fea(consts, cast(f), cast(free), cast(x))
            return (x_next.astype(jnp.float32),)

        err, comp, x_last, its, broke = _blocked(
            lambda *a: last(params, consts, *a),
            [inp["f"], inp["free"], inp["fixed"], inp["hist_in"],
             inp["inputs"][:, -1]], BLOCK)
        rows = [np.repeat(inp[k], t - 1, axis=0) for k in ("f", "free")]
        rows.append(inp["inputs"][:, :-1].reshape(n * (t - 1),
                                                  *x_last.shape[1:]))
        x_early, = _blocked(lambda *a: earlier(consts, *a), rows, ROWS)
    designs = np.concatenate([x_early.reshape(n, t - 1, *x_last.shape[1:]),
                              x_last[:, None]], axis=1)
    return {"err": err, "compliance": comp, "designs": designs,
            "cg_iters": its, "cg_broke": broke}


def gaps(served: dict, refd: dict) -> dict:
    """Per request, each compared number of ``served`` against ``refd``."""
    n = len(refd["err"])
    err_r = refd["err"].astype(np.float64)
    c_r = refd["compliance"].astype(np.float64)
    return {
        "err_gap": np.abs(served["err"] - err_r) / err_r,
        "compliance_gap": np.abs(served["compliance"] - c_r) / c_r,
        "density_gap": np.abs(served["designs"] - refd["designs"]).reshape(
            n, -1).max(axis=1)}


def verdict(per_request: dict, limits: dict):
    """(ok, [(name, worst value, limit), ...])."""
    numbers = [(k, float(np.max(per_request[k])), float(limits[k]))
               for k in NAMES]
    return all(np.isfinite(v) and v <= lim for _, v, lim in numbers), numbers


def run(cfg: dict, ref, seed: int, picked: list, tap, rows=None,
        variants: bool = False):
    """Replay ``picked`` requests with the reference module ``ref``; ``tap``
    is the ``bench.tap.Tap`` that watched the window. Returns (ok,
    [(name, value, limit), ...], variants): with a list ``rows``, appends
    one dict of readings per request; with ``variants``, the third item
    maps each control and planted fault to its (ok, numbers), else it is
    empty."""
    import jax.numpy as jnp

    nelx, nely = cfg["nelx"], cfg["nely"]
    tapped = [tap.lane_values(r["uid"]) for r in picked]
    if not picked or any(t is None for t in tapped):
        return False, [("requests_replayed", float(sum(
            t is not None for t in tapped)), float(len(picked)))], {}
    # what was served (the request's density and compliance) and, from the
    # tap, the gate's error and the designs before the served one
    x_p = np.stack([np.asarray(r["density"], np.float32) for r in picked])
    hist = np.stack([t[1] for t in tapped])
    oldest = np.stack([t[2] for t in tapped])
    frames = np.concatenate([oldest[:, None], hist, x_p[:, None]], axis=1)
    loads = [ref.point_load(nelx, nely, r["load_x"], r["fy"]) for r in picked]
    f, free, fixed = (np.stack(a) for a in zip(*loads))
    inp = {"f": f, "free": free, "fixed": fixed,
           # the history the surrogate saw at the last iteration:
           # x[n-11] .. x[n-2]
           "hist_in": frames[:, :-2], "inputs": frames[:, :-1]}
    served = {"err": np.asarray([t[0] for t in tapped], np.float64),
              "compliance": np.asarray([r["compliance"] for r in picked],
                                       np.float64),
              "designs": frames[:, 1:]}
    refd = replay(cfg, ref, seed, inp, jnp.float32)
    per = gaps(served, refd)
    if rows is not None:
        for i, r in enumerate(picked):
            rows.append({"uid": r["uid"], "load_x": r["load_x"],
                         "n_iter": r["n_iter"],
                         "cg_breakdowns": r["cg_breakdowns"],
                         "ref_cg_iters": int(refd["cg_iters"][i]),
                         "ref_cg_broke": bool(refd["cg_broke"][i]),
                         "err": float(served["err"][i]),
                         "err_ref": float(refd["err"][i]),
                         **{k: float(per[k][i]) for k in NAMES}})
    ok, numbers = verdict(per, cfg["limits"])
    if not variants:
        return ok, numbers, {}
    out = {}
    # the control: the reference in the system's place, in bfloat16, the
    # precision below the configuration's float32; and, for a reading
    # only, float32 with filter + OC alone in bfloat16
    for name, dt, fdt in (("control_bf16", jnp.bfloat16, None),
                          ("filter_oc_bf16", jnp.float32, jnp.bfloat16)):
        low = replay(cfg, ref, seed, inp, dt, fdt)
        out[name] = verdict(gaps(low, refd), cfg["limits"])
    # an answer altered where it is produced (the served design), and
    # each replayed step returning its input design unchanged
    altered = dict(served, designs=frames[:, 1:].copy())
    altered["designs"][:, -1] = np.clip(x_p + 0.05, 0.0, 1.0)
    out["altered_answer"] = verdict(gaps(altered, refd), cfg["limits"])
    held = dict(served, designs=frames[:, :-1])
    out["unchanged_step"] = verdict(gaps(held, refd), cfg["limits"])
    return ok, numbers, out
