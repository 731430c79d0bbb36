"""2D plane-stress FEA for SIMP topology optimization, pure JAX.

Classic 88-line-topopt formulation (Andreassen et al. 2011): bilinear quad
elements, unit thickness, E0=1, nu=0.3. The global stiffness solve is
matrix-free preconditioned CG (gather element dofs -> dense 8x8 KE apply
-> scatter-add), jit/vmap friendly and differentiable.

This is the paper's baseline: CRONet approximates exactly this solver
inside the optimization loop (paper §II-A).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def element_stiffness(nu: float = 0.3) -> np.ndarray:
    """Standard 8x8 bilinear quad KE (E=1, unit thickness)."""
    k = np.array([
        1 / 2 - nu / 6, 1 / 8 + nu / 8, -1 / 4 - nu / 12, -1 / 8 + 3 * nu / 8,
        -1 / 4 + nu / 12, -1 / 8 - nu / 8, nu / 6, 1 / 8 - 3 * nu / 8,
    ])
    KE = 1 / (1 - nu ** 2) * np.array([
        [k[0], k[1], k[2], k[3], k[4], k[5], k[6], k[7]],
        [k[1], k[0], k[7], k[6], k[5], k[4], k[3], k[2]],
        [k[2], k[7], k[0], k[5], k[6], k[3], k[4], k[1]],
        [k[3], k[6], k[5], k[0], k[7], k[2], k[1], k[4]],
        [k[4], k[5], k[6], k[7], k[0], k[1], k[2], k[3]],
        [k[5], k[4], k[3], k[2], k[1], k[0], k[7], k[6]],
        [k[6], k[3], k[4], k[1], k[2], k[7], k[0], k[5]],
        [k[7], k[2], k[1], k[4], k[3], k[6], k[5], k[0]],
    ])
    return KE


class Problem(NamedTuple):
    nelx: int
    nely: int
    edof: jnp.ndarray          # (ne, 8) global dof indices per element
    free_mask: jnp.ndarray     # (ndof,) 1.0 on free dofs, 0.0 on fixed
    f: jnp.ndarray             # (ndof,) load vector
    KE: jnp.ndarray            # (8, 8)
    volfrac: float
    fixed_x_mask: jnp.ndarray  # (ndof,) bookkeeping for the load volume
    penal: float = 3.0
    e_min: float = 1e-9
    # shape-class padding: 1.0 on active elements, 0.0 on the passive
    # border rows/cols pad_problem adds. None (the default) means every
    # element is active — the pre-shape-class layout, and the path every
    # existing caller stays on.
    elem_mask: Optional[jnp.ndarray] = None   # (nely, nelx) or None


def _edof_matrix(nelx: int, nely: int) -> np.ndarray:
    """Node numbering column-major (x-fast in elements), 2 dof per node —
    standard 88-line layout: node id n = x*(nely+1) + y."""
    edof = np.zeros((nelx * nely, 8), dtype=np.int32)
    for ex in range(nelx):
        for ey in range(nely):
            el = ex * nely + ey
            n1 = (nely + 1) * ex + ey
            n2 = (nely + 1) * (ex + 1) + ey
            edof[el] = [2 * n1, 2 * n1 + 1, 2 * n2, 2 * n2 + 1,
                        2 * n2 + 2, 2 * n2 + 3, 2 * n1 + 2, 2 * n1 + 3]
    return edof


def mbb_problem(nelx: int, nely: int, volfrac: float = 0.5) -> Problem:
    """MBB half-beam: unit downward load at top-left node; x symmetry on the
    left edge; y support at bottom-right node (paper's benchmark)."""
    return point_load_problem(nelx, nely, volfrac=volfrac)


def point_load_problem(nelx: int, nely: int, load_node=(0, 0),
                       load=(0.0, -1.0), volfrac: float = 0.5) -> Problem:
    """MBB-style boundary conditions with a parameterizable point load —
    the per-request degree of freedom the serving queue exercises (one
    load case per bridge/monitoring event, paper's digital-twin framing).

    load_node: (x, y) grid coordinates of the loaded node; load: (Fx, Fy).
    ``point_load_problem(nelx, nely)`` reproduces ``mbb_problem(nelx, nely)``.
    """
    xn, yn = load_node
    if not (0 <= xn <= nelx and 0 <= yn <= nely):
        raise ValueError(f"load node {load_node} outside {nelx}x{nely} grid")
    ndof = 2 * (nelx + 1) * (nely + 1)
    node = xn * (nely + 1) + yn
    f = np.zeros(ndof)
    f[2 * node] = load[0]
    f[2 * node + 1] = load[1]
    fixed = list(range(0, 2 * (nely + 1), 2))      # left edge x-dofs
    fixed.append(2 * (nelx + 1) * (nely + 1) - 1)  # bottom-right y
    free_mask = np.ones(ndof)
    free_mask[fixed] = 0.0
    fixed_x = np.zeros(ndof)
    fixed_x[fixed] = 1.0
    if not np.any(f * free_mask):
        raise ValueError(
            f"load {load} at node {load_node} acts only on fixed dofs — "
            "the problem would be all-zero (use idle_problem for padding)")
    return Problem(
        nelx=nelx, nely=nely,
        edof=jnp.asarray(_edof_matrix(nelx, nely)),
        free_mask=jnp.asarray(free_mask),
        f=jnp.asarray(f * free_mask),
        KE=jnp.asarray(element_stiffness()),
        volfrac=volfrac,
        fixed_x_mask=jnp.asarray(fixed_x),
    )


def stiffness_apply(prob: Problem, x_phys: jnp.ndarray, u: jnp.ndarray):
    """Matrix-free K(x) @ u with SIMP interpolation E = Emin + x^p (1-Emin).
    Passive elements (elem_mask == 0, shape-class padding) carry exactly
    zero stiffness, so the padded border is fully decoupled."""
    e = prob.e_min + (x_phys.reshape(-1) ** prob.penal) * (1 - prob.e_min)
    if prob.elem_mask is not None:
        e = e * prob.elem_mask.reshape(-1)
    ue = u[prob.edof]                              # (ne, 8)
    fe = jnp.einsum("e,ij,ej->ei", e, prob.KE, ue)  # (ne, 8)
    out = jnp.zeros_like(u).at[prob.edof.reshape(-1)].add(fe.reshape(-1))
    return out * prob.free_mask


def solve(prob: Problem, x_phys: jnp.ndarray, tol: float = 1e-6,
          max_iter: int = 2000):
    """Jacobi-preconditioned CG on the free dofs, from zero (see
    ``solve_b``). Returns (u, n_iters, broke): ``broke`` is True when the
    solve stopped at a CG breakdown (see ``solve_b``) instead of meeting
    ``tol`` or ``max_iter``."""
    f = prob.f * prob.free_mask
    # diagonal of K for Jacobi preconditioner
    e = prob.e_min + (x_phys.reshape(-1) ** prob.penal) * (1 - prob.e_min)
    if prob.elem_mask is not None:
        e = e * prob.elem_mask.reshape(-1)
    diag_e = jnp.einsum("e,i->ei", e, jnp.diag(prob.KE))
    diag = jnp.zeros_like(f).at[prob.edof.reshape(-1)].add(diag_e.reshape(-1))
    diag = jnp.where(diag > 0, diag, 1.0)

    def precond(r):
        return r / diag * prob.free_mask

    u = jnp.zeros_like(f)
    r = f
    z = precond(r)
    p = z
    rz = jnp.vdot(r, z)
    fnorm = jnp.linalg.norm(f)

    def cond(state):
        u, r, p, rz, it, ok = state
        return ok & (jnp.linalg.norm(r) > tol * fnorm) & (it < max_iter)

    def body(state):
        u, r, p, rz, it, ok = state
        kp = stiffness_apply(prob, x_phys, p)
        pkp = jnp.vdot(p, kp)
        # breakdown stop (see solve_b): keep the last finite iterate
        ok = pkp > 0
        alpha = rz / jnp.maximum(pkp, 1e-30)
        u_n = u + alpha * p
        r_n = r - alpha * kp
        z = precond(r_n)
        rz_new = jnp.vdot(r_n, z)
        p_n = z + (rz_new / jnp.maximum(rz, 1e-30)) * p
        return (jnp.where(ok, u_n, u), jnp.where(ok, r_n, r),
                jnp.where(ok, p_n, p), jnp.where(ok, rz_new, rz),
                it + ok.astype(jnp.int32), ok)

    u, r, p, rz, it, ok = jax.lax.while_loop(
        cond, body, (u, r, p, rz, jnp.zeros((), jnp.int32), fnorm >= 0))
    return u, it, ~ok


def compliance_and_sens(prob: Problem, x_phys: jnp.ndarray, u: jnp.ndarray):
    """Compliance c = u^T K u and sensitivity dc/dx (SIMP adjoint)."""
    ue = u[prob.edof]
    ce = jnp.einsum("ei,ij,ej->e", ue, prob.KE, ue)       # (ne,)
    xf = x_phys.reshape(-1)
    e = prob.e_min + xf ** prob.penal * (1 - prob.e_min)
    if prob.elem_mask is not None:
        # passive padding: zero energy AND zero sensitivity — border
        # elements touch active nodes, so ce alone is not zero there
        m = prob.elem_mask.reshape(-1)
        e = e * m
        ce_s = ce * m
    else:
        ce_s = ce
    c = tree_sum(e * ce)    # batch-invariant: serving slots report the
    # exact compliance a standalone run reports
    dc = -prob.penal * xf ** (prob.penal - 1) * (1 - prob.e_min) * ce_s
    return c, dc.reshape(x_phys.shape)


def load_volume(prob: Problem) -> jnp.ndarray:
    """(4, nely+1, nelx+1, 1) TrunkNet input: [Fx, Fy, supp_x, supp_y]
    stacked on the depth axis (configs/cronet.py reconstruction)."""
    return _load_volume(prob.f, prob.fixed_x_mask, prob.nelx, prob.nely)


def _load_volume(f, fixed_x_mask, nelx: int, nely: int) -> jnp.ndarray:
    ny, nx = nely + 1, nelx + 1
    fx = f[0::2].reshape(nx, ny).T
    fy = f[1::2].reshape(nx, ny).T
    sx = fixed_x_mask[0::2].reshape(nx, ny).T
    sy = fixed_x_mask[1::2].reshape(nx, ny).T
    vol = jnp.stack([fx, fy, sx, sy], axis=0)             # (4, ny, nx)
    return vol[..., None]


# ---------------------------------------------------------------------------
# Batch axis — stacked problems sharing one mesh, for the slot-batched
# topology-optimization service (serve/topo_service.py). Everything here is
# bitwise batch-invariant on CPU: slot b of a B-wide call produces exactly
# the arrays a standalone single-problem call produces (verified by
# tests/test_topo_service.py).
# ---------------------------------------------------------------------------


def tree_sum(x, axis: int = -1):
    """Batch-invariant sum: fixed balanced-tree pairwise reduction.

    XLA's native row reductions (einsum "bi,bi->b", jnp.linalg.norm,
    jnp.sum over a feature axis) pick different partial-sum orders for
    different batch widths on CPU, so slot b of a B-wide reduction is not
    bitwise-equal to the same reduction at B=1. This zero-pads the reduced
    axis to a power of two and folds halves with elementwise adds — every
    output element sums its inputs in one fixed tree order regardless of
    the surrounding batch shape. O(log n) elementwise passes; used for the
    long reductions in the serving-critical loop.
    """
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    p = 1 << max(n - 1, 0).bit_length()
    if p != n:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, p - n)]
        x = jnp.pad(x, pad)
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def tree_dot(a, b, axis: int = -1):
    """Batch-invariant dot product along `axis` (see tree_sum)."""
    return tree_sum(a * b, axis=axis)


def tree_norm(a, axis: int = -1):
    """Batch-invariant L2 norm along `axis` (see tree_sum)."""
    return jnp.sqrt(tree_sum(a * a, axis=axis))


def pad_problem(prob: Problem, nelx: int, nely: int) -> Problem:
    """Embed ``prob`` into a larger canonical ``(nelx, nely)`` mesh with
    a PASSIVE border — the mesh shape-class mechanism: the gateway pads
    nearby discretizations onto one canonical mesh so its compile cache
    grows with the number of shape classes, not the fleet.

    The padding is inert by construction: padded elements carry an
    ``elem_mask`` of 0.0 (exactly zero stiffness, energy, and
    sensitivity — see ``_e_grid``/``compliance_and_sens_b``), padded
    dofs are fixed (zero load, zero displacement), the filter normalizes
    over active neighbours only, and the OC update freezes padded
    densities at 0 with the volume constraint taken over active
    elements (fea/simp.py). An exact-fit mesh returns the problem with
    an all-ones mask attached (the same physics, compiled through the
    masked step family), so one shape-class engine serves padded and
    exact-fit requests uniformly.

    Note the result is a DIFFERENT discretization of the same load
    case: densities served on a shape class are bitwise-reproducible
    against any engine of that class (the serving contract), not
    against the original unpadded mesh. ``crop_density`` maps the
    padded design back to the original mesh's layout.
    """
    ox, oy = prob.nelx, prob.nely
    if nelx < ox or nely < oy:
        raise ValueError(f"cannot pad {ox}x{oy} onto smaller shape "
                         f"class {nelx}x{nely}")
    # element grid is [ex, ey]; the density-layout (nely, nelx) shape is
    # the same C-order buffer reinterpreted (flat el = ex*nely + ey,
    # matching _e_grid's reshape-not-transpose convention)
    mask_g = np.zeros((nelx, nely), np.float32)
    mask_g[:ox, :oy] = 1.0
    elem_mask = jnp.asarray(mask_g.reshape(nely, nelx))
    if (nelx, nely) == (ox, oy):
        return prob._replace(elem_mask=elem_mask)

    def embed(vec, fill):
        g = np.full((nelx + 1, nely + 1, 2), fill, np.float64)
        g[:ox + 1, :oy + 1] = np.asarray(vec).reshape(ox + 1, oy + 1, 2)
        return jnp.asarray(g.reshape(-1))

    return Problem(
        nelx=nelx, nely=nely, edof=jnp.asarray(_edof_matrix(nelx, nely)),
        free_mask=embed(prob.free_mask, 0.0),   # padded dofs are fixed
        f=embed(prob.f, 0.0),
        KE=prob.KE, volfrac=prob.volfrac,
        # padding reads as supported in the TrunkNet load volume — it IS
        # a fully-constrained region of the padded problem
        fixed_x_mask=embed(prob.fixed_x_mask, 1.0),
        penal=prob.penal, e_min=prob.e_min, elem_mask=elem_mask)


def crop_density(x, orig_nelx: int, orig_nely: int) -> np.ndarray:
    """Crop a padded-mesh density field back to the original mesh's
    density layout (the design-field inverse of ``pad_problem``)."""
    nely, nelx = x.shape
    if (nelx, nely) == (orig_nelx, orig_nely):
        return np.asarray(x)
    if nelx < orig_nelx or nely < orig_nely:
        raise ValueError(f"density {nelx}x{nely} smaller than original "
                         f"mesh {orig_nelx}x{orig_nely}")
    g = np.asarray(x).reshape(nelx, nely)[:orig_nelx, :orig_nely]
    return g.reshape(orig_nely, orig_nelx)


def idle_problem(nelx: int, nely: int, volfrac: float = 0.5) -> Problem:
    """Zero-load, fully-fixed padding problem for empty serving slots: the
    masked batched CG treats it as converged in zero iterations, so it
    costs (almost) nothing to carry in a batch."""
    ndof = 2 * (nelx + 1) * (nely + 1)
    zeros = jnp.zeros((ndof,))
    return Problem(
        nelx=nelx, nely=nely, edof=jnp.asarray(_edof_matrix(nelx, nely)),
        free_mask=zeros, f=zeros, KE=jnp.asarray(element_stiffness()),
        volfrac=volfrac, fixed_x_mask=zeros)


class BatchProblem(NamedTuple):
    """B load cases stacked on a shared (nelx, nely) mesh. edof/KE/penalty
    are mesh properties and stay unbatched; loads and supports are per-slot."""
    nelx: int
    nely: int
    edof: jnp.ndarray          # (ne, 8) shared
    KE: jnp.ndarray            # (8, 8) shared
    f: jnp.ndarray             # (B, ndof)
    free_mask: jnp.ndarray     # (B, ndof)
    fixed_x_mask: jnp.ndarray  # (B, ndof)
    volfrac: jnp.ndarray       # (B,)
    penal: float = 3.0
    e_min: float = 1e-9
    # per-slot active-element masks for shape-class padding; None keeps
    # the pre-shape-class pytree shape (and compiled-step signatures)
    elem_mask: Optional[jnp.ndarray] = None   # (B, nely, nelx) or None

    @property
    def batch(self) -> int:
        return self.f.shape[0]


def stack_problems(probs) -> BatchProblem:
    """Stack same-mesh Problems into a BatchProblem (slot order preserved).
    If ANY problem carries an elem_mask, every slot gets one (all-ones
    for mask-less problems — the same physics, every masking op reduces
    to a multiply by 1.0; the batch compiles via the masked step
    family)."""
    p0 = probs[0]
    for p in probs[1:]:
        if (p.nelx, p.nely) != (p0.nelx, p0.nely):
            raise ValueError("all problems in a batch must share one mesh; "
                             f"got {p.nelx}x{p.nely} vs {p0.nelx}x{p0.nely}")
        if p.penal != p0.penal or p.e_min != p0.e_min:
            raise ValueError("SIMP penalty/e_min must match across a batch")
    elem_mask = None
    if any(p.elem_mask is not None for p in probs):
        ones = jnp.ones((p0.nely, p0.nelx), jnp.float32)
        elem_mask = jnp.stack([ones if p.elem_mask is None
                               else jnp.asarray(p.elem_mask, jnp.float32)
                               for p in probs])
    return BatchProblem(
        nelx=p0.nelx, nely=p0.nely, edof=p0.edof, KE=p0.KE,
        f=jnp.stack([p.f for p in probs]),
        free_mask=jnp.stack([p.free_mask for p in probs]),
        fixed_x_mask=jnp.stack([p.fixed_x_mask for p in probs]),
        volfrac=jnp.asarray([p.volfrac for p in probs]),
        penal=p0.penal, e_min=p0.e_min, elem_mask=elem_mask,
    )


def _ke_apply(KE, ue):
    """(KE @ ue_e) per element with a fixed, unrolled contraction order —
    a dot_general here lowers differently per batch width. ue: (..., 8)."""
    acc = ue[..., 0:1] * KE[:, 0]
    for j in range(1, 8):
        acc = acc + ue[..., j:j + 1] * KE[:, j]
    return acc


def _simp_e(bp: BatchProblem, X):
    e = bp.e_min + (X.reshape(X.shape[0], -1) ** bp.penal) * (1 - bp.e_min)
    if bp.elem_mask is not None:
        e = e * bp.elem_mask.reshape(X.shape[0], -1)
    return e


def _ue_slices(Ug):
    """Element-local dofs as pure slices of the (B, nelx+1, nely+1, 2) dof
    grid, in the 88-line edof local order [n1 n2 n3 n4] x [x y]. The quad
    mesh is structured, so the per-trip gathers of a U[:, edof] formulation
    (XLA CPU gathers cost ~10ns/element and dominate the CG body) reduce
    to free slicing. Returns (B, nelx, nely, 8)."""
    n1 = Ug[:, :-1, :-1, :]        # node (ex,   ey)
    n2 = Ug[:, 1:, :-1, :]         # node (ex+1, ey)
    n3 = Ug[:, 1:, 1:, :]          # node (ex+1, ey+1)
    n4 = Ug[:, :-1, 1:, :]         # node (ex,   ey+1)
    return jnp.concatenate([n1, n2, n3, n4], axis=-1)


def _assemble(fe):
    """Scatter-free assembly: per-element dof contributions fe
    (B, nelx, nely, 8) -> nodal dof grid (B, nelx+1, nely+1, 2) by adding
    four zero-padded shifted slices in one fixed order. XLA's scatter-add
    accumulates duplicate indices in a lowering-defined order that changes
    with batch width; this is deterministic (and much faster)."""
    z = ((0, 0),)
    c1 = jnp.pad(fe[..., 0:2], (*z, (0, 1), (0, 1), *z))
    c2 = jnp.pad(fe[..., 2:4], (*z, (1, 0), (0, 1), *z))
    c3 = jnp.pad(fe[..., 4:6], (*z, (1, 0), (1, 0), *z))
    c4 = jnp.pad(fe[..., 6:8], (*z, (0, 1), (1, 0), *z))
    return (c1 + c2) + (c3 + c4)


def _e_grid(bp: BatchProblem, X):
    """SIMP stiffness per element on the (nelx, nely) element grid, using
    the same flat element indexing as the single-problem path (reshape,
    not transpose — matches stiffness_apply's x_phys.reshape(-1)).
    Passive padding elements (elem_mask == 0) get exactly zero stiffness."""
    B, nely, nelx = X.shape
    e = bp.e_min + (X.reshape(B, nelx, nely) ** bp.penal) * (1 - bp.e_min)
    if bp.elem_mask is not None:
        e = e * bp.elem_mask.reshape(B, nelx, nely)
    return e


def stiffness_apply_b(bp: BatchProblem, X, U):
    """Batched matrix-free K(x) u. X: (B, nely, nelx); U: (B, ndof)."""
    B, nely, nelx = X.shape
    Ug = U.reshape(B, nelx + 1, nely + 1, 2)
    fe = _e_grid(bp, X)[..., None] * _ke_apply(bp.KE, _ue_slices(Ug))
    return _assemble(fe).reshape(B, -1) * bp.free_mask


def compliance_and_sens_b(bp: BatchProblem, X, U):
    """Batched compliance + SIMP sensitivity. Returns ((B,), (B, nely, nelx))."""
    B, nely, nelx = X.shape
    ue = _ue_slices(U.reshape(B, nelx + 1, nely + 1, 2))
    ce = tree_sum(ue * _ke_apply(bp.KE, ue), axis=-1)   # (B, nelx, nely)
    ce = ce.reshape(B, -1)                              # el = ex*nely + ey
    e = _simp_e(bp, X)
    c = tree_sum(e * ce, axis=-1)
    xf = X.reshape(B, -1)
    if bp.elem_mask is not None:
        # border padding elements share nodes with active ones, so their
        # raw ce is nonzero — the sensitivity must be masked explicitly
        ce = ce * bp.elem_mask.reshape(B, -1)
    dc = -bp.penal * xf ** (bp.penal - 1) * (1 - bp.e_min) * ce
    return c, dc.reshape(X.shape)


def load_volume_b(bp: BatchProblem) -> jnp.ndarray:
    """(B, 4, nely+1, nelx+1, 1) TrunkNet inputs, one per slot."""
    return jax.vmap(lambda f, m: _load_volume(f, m, bp.nelx, bp.nely))(
        bp.f, bp.fixed_x_mask)


def solve_b(bp: BatchProblem, X, tol: float = 1e-6, max_iter: int = 2000,
            need=None, backend: str = "reference"):
    """Batched Jacobi-preconditioned CG with per-slot convergence masking.

    Same update recurrence as ``solve``: each slot performs the identical
    update sequence at any batch width, then freezes (masked out of the
    while-loop body) once its own residual criterion is met — so results
    are bitwise slot-invariant, while the loop trip count is the max over
    the still-active slots. A slot with f == 0 (an empty serving slot)
    converges in zero iterations. `need` (bool (B,)) marks slots whose
    solution the caller will actually consume; the others are masked out
    immediately so they burn zero iterations (their U stays zero).
    Returns (U, per-slot iters, per-slot broke).

    Every solve starts from zero. Warm starts do harm here: with SIMP's
    1e9 stiffness contrast an error in the near-floating void regions
    barely moves the residual, so a warm start keeps it, and where the
    solve stops unconverged (the 60x20 mesh, mostly at ``max_iter``) it
    grows from solve to solve (up to 2000x the converged field's
    displacement). From zero, U depends on the design alone, and a
    surrogate prediction never leaks into the FEA check that scores it.

    Breakdown: with SIMP's 1e9 stiffness contrast, f32 rounding can
    give a search direction a curvature ``p.Kp <= 0`` (or NaN) once the
    residual stagnates; the step ``rz / p.Kp`` then overflows and the
    NaN reaches the design. Such a slot stops at its last finite iterate,
    which has NOT met ``tol``, and its ``broke`` flag is set, so callers
    can tell it from a converged slot (the hybrid state counts them per
    request, the dataset builder drops them as training targets). A slot
    that stopped at ``max_iter`` is the other unconverged case; its
    iteration count says so.

    ``backend`` selects the iteration engine: ``"reference"`` is this
    pure-XLA loop; ``"fused"`` dispatches to kernels/cg_fused.py, which
    runs the ENTIRE convergence loop inside one pallas_call — results
    bitwise-equal to this path under jit (the serving tick's context;
    see the cg_fused module docstring for why jit is the contract's
    domain), one kernel launch per solve.
    """
    if backend == "fused":
        from repro.kernels import cg_fused
        return cg_fused.solve_b_fused(bp, X, tol=tol, max_iter=max_iter,
                                      need=need)
    if backend != "reference":
        raise ValueError(f"unknown CG backend {backend!r} "
                         "(expected 'reference' or 'fused')")
    F = bp.f * bp.free_mask
    diag_e = _e_grid(bp, X)[..., None] * jnp.diag(bp.KE)[None, None, None, :]
    diag = _assemble(diag_e).reshape(X.shape[0], -1)
    diag = jnp.where(diag > 0, diag, 1.0)
    if need is None:
        need = jnp.ones((F.shape[0],), bool)

    def precond(R):
        return R / diag * bp.free_mask

    U = jnp.zeros_like(F)
    R = F
    Z = precond(R)
    RZ = tree_dot(R, Z)
    fnorm = tree_norm(F)

    def active_of(R, its, ok):
        return need & ok & (tree_norm(R) > tol * fnorm) & (its < max_iter)

    def cond(state):
        U, R, P, RZ, its, ok = state
        return jnp.any(active_of(R, its, ok))

    def body(state):
        U, R, P, RZ, its, ok = state
        KP = stiffness_apply_b(bp, X, P)
        pKp = tree_dot(P, KP)
        # breakdown stop (docstring): only an active slot can break down;
        # a frozen one keeps its last P, so its pKp says nothing new
        act = active_of(R, its, ok)
        good = pKp > 0
        ok = ok & (good | ~act)
        act = act & good
        alpha = RZ / jnp.maximum(pKp, 1e-30)
        U_n = U + alpha[:, None] * P
        R_n = R - alpha[:, None] * KP
        Z = precond(R_n)
        RZ_n = tree_dot(R_n, Z)
        P_n = Z + (RZ_n / jnp.maximum(RZ, 1e-30))[:, None] * P
        m = act[:, None]
        return (jnp.where(m, U_n, U), jnp.where(m, R_n, R),
                jnp.where(m, P_n, P), jnp.where(act, RZ_n, RZ),
                its + act.astype(jnp.int32), ok)

    B = F.shape[0]
    U, R, P, RZ, its, ok = jax.lax.while_loop(
        cond, body, (U, R, Z, RZ, jnp.zeros((B,), jnp.int32),
                     jnp.ones((B,), bool)))
    return U, its, ~ok
