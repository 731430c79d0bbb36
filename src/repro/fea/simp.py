"""SIMP topology-optimization loop (sensitivity filter + OC update).

The driver the paper accelerates: each iteration needs one FEA solve whose
displacement field CRONet learns to predict (fea/hybrid.py swaps the
solver for the surrogate after warm-up).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.fea import fea2d


def make_filter(nelx: int, nely: int, rmin: float = 1.5):
    """Sensitivity filter weights as a small static convolution kernel.

    The returned ``apply(x, dc, mask=None)`` accepts an optional
    active-element mask (shape-class padding): the weight normalization
    then counts active neighbours only (``conv(mask)`` instead of
    ``conv(ones)``) and the filtered sensitivity is zeroed on passive
    elements. ``mask=None`` is the exact pre-mask code path. An
    all-ones mask is mathematically the same filter but NOT bitwise
    (``conv(ones_like(x))`` is constant-folded at compile time while
    ``conv(mask)`` is evaluated at runtime — last-ulp differences);
    bitwise contracts therefore hold WITHIN a masked or unmasked
    serving path, never across the two."""
    r = int(np.ceil(rmin)) - 1
    ks = 2 * r + 1
    wy, wx = np.meshgrid(np.arange(-r, r + 1), np.arange(-r, r + 1), indexing="ij")
    w = np.maximum(0.0, rmin - np.sqrt(wx ** 2 + wy ** 2))
    kernel = jnp.asarray(w[..., None, None])  # (ks, ks, 1, 1)

    def conv(a):
        return jax.lax.conv_general_dilated(
            a[None, ..., None], kernel, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))[0, ..., 0]

    def apply(x, dc, mask=None):
        """Classic sensitivity filter: dc~ = conv(w * x * dc) / (x * conv(w))."""
        num = conv(x * dc)
        den = conv(jnp.ones_like(x) if mask is None else mask)
        out = num / jnp.maximum(den * jnp.maximum(x, 1e-3), 1e-9)
        return out if mask is None else out * mask

    return apply


def oc_update(x, dc, dv, volfrac, move: float = 0.2, mask=None):
    """Optimality-criteria update with bisection on the Lagrange multiplier.

    With an active-element ``mask`` (shape-class padding) the passive
    densities are frozen at 0 and the volume constraint is taken over
    ACTIVE elements only — ``volfrac`` keeps its meaning on the original
    mesh. ``mask=None`` is the exact pre-mask path (bitwise contracts
    hold within a masked or unmasked serving path, not across them)."""

    def xnew(lmid):
        be = jnp.sqrt(jnp.maximum(-dc / (dv * lmid), 1e-30))
        xn = x * be
        xn = jnp.clip(xn, x - move, x + move)
        xn = jnp.clip(xn, 0.001, 1.0)
        return xn if mask is None else xn * mask

    active = (float(x.size) if mask is None
              else jnp.maximum(fea2d.tree_sum(mask.reshape(-1)), 1.0))

    def body(state, _):
        l1, l2 = state
        lmid = 0.5 * (l1 + l2)
        # batch-invariant volume sum: the bisection COMPARES the mean, so a
        # last-ulp batch-width difference would fork the whole multiplier
        # search; tree_sum keeps serving slots bitwise-equal to solo runs
        vol = fea2d.tree_sum(xnew(lmid).reshape(-1)) / active
        too_much = vol > volfrac
        l1 = jnp.where(too_much, lmid, l1)
        l2 = jnp.where(too_much, l2, lmid)
        return (l1, l2), None

    (l1, l2), _ = jax.lax.scan(body, (jnp.asarray(1e-9), jnp.asarray(1e9)),
                               None, length=60)
    return xnew(0.5 * (l1 + l2))


def make_filter_b(nelx: int, nely: int, rmin: float = 1.5,
                  masked: bool = False):
    """Batched sensitivity filter: (B, nely, nelx) densities/sensitivities.
    vmap of the single-problem filter — the conv is bitwise batch-invariant
    on CPU, which the batched serving path relies on. With ``masked=True``
    the returned callable takes ``(X, DC, mask)`` with a per-slot
    (B, nely, nelx) active-element mask (shape-class serving)."""
    apply = make_filter(nelx, nely, rmin)
    if masked:
        return jax.vmap(lambda x, dc, m: apply(x, dc, m))
    return jax.vmap(apply)


def oc_update_b(X, DC, dv, volfrac, move: float = 0.2, mask=None):
    """Batched OC update; volfrac is per-slot (B,). X/DC: (B, nely, nelx).
    ``mask`` (optional, per-slot (B, nely, nelx)) freezes passive
    shape-class padding at density 0. ``dv`` is either one shared
    (nely, nelx) volume-gradient field or a per-slot (B, nely, nelx)
    stack — shape-class batches need the latter, because the uniform
    gradient of the mean-over-ACTIVE-elements constraint is
    ``1/active_count``, which differs per slot under padding."""
    if jnp.ndim(dv) == jnp.ndim(X):
        if mask is None:
            return jax.vmap(lambda x, dc, d, vf: oc_update(x, dc, d, vf,
                                                           move))(
                X, DC, dv, volfrac)
        return jax.vmap(lambda x, dc, d, vf, m: oc_update(x, dc, d, vf,
                                                          move, m))(
            X, DC, dv, volfrac, mask)
    if mask is None:
        return jax.vmap(lambda x, dc, vf: oc_update(x, dc, dv, vf, move))(
            X, DC, volfrac)
    return jax.vmap(lambda x, dc, vf, m: oc_update(x, dc, dv, vf, move, m))(
        X, DC, volfrac, mask)


class SimpState(NamedTuple):
    x: jnp.ndarray            # (nely, nelx) densities
    u: jnp.ndarray            # (ndof,) last displacement
    compliance: jnp.ndarray
    iteration: int


def run_simp(prob: fea2d.Problem, n_iter: int = 60, rmin: float = 1.5,
             solver: Optional[Callable] = None, record_every: int = 1,
             x0=None):
    """Reference SIMP loop. solver(x_phys) -> (u, c, dc); defaults to FEA
    (``fea2d.solve``, from zero every iteration). Returns (final_state,
    history dict of arrays)."""
    filt = make_filter(prob.nelx, prob.nely, rmin)

    def fea_solver(x_phys):
        u, _, _ = fea2d.solve(prob, x_phys)
        c, dc = fea2d.compliance_and_sens(prob, x_phys, u)
        return u, c, dc

    solver = solver or fea_solver
    x = (jnp.full((prob.nely, prob.nelx), prob.volfrac)
         if x0 is None else x0)
    dv = jnp.ones_like(x) / x.size

    xs, us, cs = [], [], []
    for it in range(n_iter):
        u, c, dc = solver(x)
        dc_f = filt(x, dc)
        x = oc_update(x, dc_f, dv, prob.volfrac)
        if it % record_every == 0:
            xs.append(np.asarray(x))
            us.append(np.asarray(u))
            cs.append(float(c))
    state = SimpState(x=x, u=u, compliance=jnp.asarray(cs[-1]), iteration=n_iter)
    return state, {"x": np.stack(xs), "u": np.stack(us), "c": np.asarray(cs)}
