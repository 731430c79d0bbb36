"""Fused batched Jacobi-PCG solve — the FEA fallback's megakernel.

Every CG iteration of the reference ``fea2d.solve_b`` bounces through
dozens of XLA op boundaries (stencil taps, assembly pads, axpy updates,
preconditioner divide, four fixed-tree reductions), each materializing
a (B, ndof) intermediate. This module fuses the ENTIRE SOLVE — the
stiffness stencil, the axpy updates, the Jacobi precondition, the
fixed-tree ``tree_dot``/``tree_norm`` reductions, the per-slot
convergence freeze mask and the convergence loop itself — into one
``pallas_call`` whose working set stays in VMEM from the first
iteration to the last. The host sees only the final displacement, the
iteration counts and the breakdown flags.

Layout. The kernel works on the reference's own flat dof vector (node
``n = x*(nely+1) + y``, dofs ``[2n, 2n+1]``), slots on sublanes and dofs
on lanes, zero-padded to whole 128-lane rows (``_lanes``; 60x20: 2688
lanes for 2562 dofs), not to the power of two that ``fea2d.tree_sum``
pads to: only a reduction's first fold sees that pad. Every block is
then a lane-dense ``(B, L)`` tile, and the structured-mesh stencil needs
no reshape: an element's eight local dofs sit at fixed lane offsets from
its first node's x-dof (its *anchor*), so gathering them is eight lane
rotations of the search direction, and assembly is four rotations back.
The SIMP stiffness lives on the anchor lanes (zero everywhere else). A
rotation's wrap around the end of the row is harmless for any
``L >= ndof``: the last anchor plus the largest local offset is
``ndof - 1``, so no anchor lane reads across the end, every wrapped
value lands on a lane whose stiffness is zero, and the assembly
rotations move only values sitting at most one lane past an anchor,
which land at most on lane ``ndof - 1``.

Arithmetic order. The kernel repeats the reference's operations in the
reference's order: the unrolled ``_ke_apply`` contraction, the
``(c1 + c2) + (c3 + c4)`` assembly sum, and the pairwise halving tree of
``tree_sum`` (the reference's zero pad added for the first fold, then
aligned lane slices down to one 128-lane row, then rotations inside
it). So under jit on the CPU, where the kernel runs through the Pallas
interpreter, ``solve_b(..., backend="fused")`` is bitwise-equal to the
reference path (tests/test_cg_fused.py sweeps widths, row widths,
``need`` masks, zero-load slots and ``elem_mask`` padding; at 23x7 it
holds only with XLA's CPU optimisation off, where LLVM contracts the
two paths' multiply-adds alike). The SIMP stiffness is recomputed
inside the kernel from the densities: handing it in precomputed changes
how XLA contracts the ``e * stencil`` products on the CPU and flips
last-ulp bits. Compiled for a TPU the same contract is not claimed;
there the path is held to a tolerance against the reference
(chip_smoke.py).

The whole slot batch rides in one grid step: a width-1 per-slot block
would lower differently from the reference's batched ops on the CPU.

Like every kernel here, ``interpret=None`` picks the Pallas interpreter
on a CPU backend and the Mosaic compiler everywhere else
(``repro.kernels.resolve_interpret``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.fea import fea2d
from repro.kernels import resolve_interpret


def _lanes(ndof: int) -> int:
    """Padded dof width: whole 128-lane rows, at least one."""
    return max(128, -(-ndof // 128) * 128)


def _local_offsets(nely: int):
    """Lane offset of each of an element's 8 local dofs (88-line edof
    order [n1 n2 n3 n4] x [x y]) from its anchor dof 2*n1."""
    col = 2 * (nely + 1)             # one node column further in x
    return (0, 1, col, col + 1, col + 2, col + 3, 2, 3)


def _tree_sum(x):
    """fea2d.tree_sum over the lanes of a (B, L) block, L a multiple of
    128, in the reference's pairing order. Returns (B, 1)."""
    n = x.shape[-1]
    p = 1 << (n - 1).bit_length()      # the reference's padded width
    if p != n:
        # the reference's zero pad, for the first fold alone. Passing the
        # lanes that meet only zeros through unchanged would sum the same,
        # but XLA on the CPU then contracts the product into that fold's
        # add where the reference does not, and the two round apart
        x = jnp.concatenate([x, jnp.zeros((x.shape[0], p - n), x.dtype)],
                            axis=1)
    while x.shape[-1] > 128:
        half = x.shape[-1] // 2
        x = x[:, :half] + x[:, half:]
    # inside one row: after folding by h, lane i holds x[i] + x[i - h];
    # lanes 64..127 then hold the reference's 64 pair sums (a + b == b + a
    # exactly), and lane 127 ends up holding the full tree
    for h in (64, 32, 16, 8, 4, 2, 1):
        x = x + pltpu.roll(x, h, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == 127, x, 0.0), axis=-1, keepdims=True)


def _make_solve_kernel(nely: int, lanes: int, tol: float, max_iter: int):
    offs = _local_offsets(nely)

    def kernel(pe_ref, ke_ref, xa_ref, am_ref, diag_ref, free_ref, need_ref,
               fnorm_ref, r_ref, p_ref, rz_ref, uo_ref, itso_ref, broke_ref):
        penal, e_min = pe_ref[0], pe_ref[1]
        diag = diag_ref[...]            # (B, L) Jacobi diagonal
        free = free_ref[...]            # (B, L)
        need = need_ref[...]            # (B, 1) float 0/1
        fnorm = fnorm_ref[...]          # (B, 1)
        # SIMP stiffness on the anchor lanes, recomputed in-kernel exactly
        # as fea2d._e_grid does (the anchor mask carries elem_mask, and
        # zero off the anchors)
        e = (e_min + (xa_ref[...] ** penal) * (1 - e_min)) * am_ref[...]
        ke = [[ke_ref[i, j] for j in range(8)] for i in range(8)]

        def shift(v, k):                # out[a] = v[a - k] (cyclic)
            return pltpu.roll(v, k % lanes, 1)

        def stiffness_apply(P):
            ue = [shift(P, -o) for o in offs]          # ue[j][a] = P[a + o_j]
            fe = []
            for i in range(8):                         # fea2d._ke_apply order
                acc = ue[0] * ke[i][0]
                for j in range(1, 8):
                    acc = acc + ue[j] * ke[i][j]
                fe.append(e * acc)
            # per node pair: x-dof from the even lane, y-dof one lane up
            pair = [fe[2 * k] + shift(fe[2 * k + 1], 1) for k in range(4)]
            c1, c2, c3, c4 = (shift(pair[k], offs[2 * k]) for k in range(4))
            return ((c1 + c2) + (c3 + c4)) * free

        def active_of(rnorm, its, ok):
            # the reference criterion with rnorm carried instead of
            # re-reduced; ok == 0 marks a CG breakdown (fea2d.solve_b
            # docstring)
            return ((need > 0) & (ok > 0) & (rnorm > tol * fnorm)
                    & (its < max_iter))

        def cond(state):
            _, _, _, _, its, rnorm, ok = state
            return jnp.sum(active_of(rnorm, its, ok).astype(jnp.int32)) > 0

        def body(state):
            U, R, P, RZ, its, rnorm, ok = state
            KP = stiffness_apply(P)
            pKp = _tree_sum(P * KP)
            # only an active slot can break down (fea2d.solve_b)
            act = active_of(rnorm, its, ok)
            good = pKp > 0
            ok = jnp.where(good | ~act, ok, 0)
            act = act & good
            alpha = RZ / jnp.maximum(pKp, 1e-30)
            U_n = U + alpha * P
            R_n = R - alpha * KP
            Z = R_n / diag * free       # Jacobi precondition
            RZ_n = _tree_sum(R_n * Z)
            P_n = Z + (RZ_n / jnp.maximum(RZ, 1e-30)) * P
            R_out = jnp.where(act, R_n, R)
            # next trip's convergence test, while R is still in VMEM
            return (jnp.where(act, U_n, U), R_out, jnp.where(act, P_n, P),
                    jnp.where(act, RZ_n, RZ), its + act.astype(jnp.int32),
                    jnp.sqrt(_tree_sum(R_out * R_out)), ok)

        # from zero: U = 0, R = F, so the first residual norm is fnorm
        state0 = (jnp.zeros_like(diag), r_ref[...], p_ref[...], rz_ref[...],
                  jnp.zeros(need.shape, jnp.int32), fnorm,
                  jnp.ones(need.shape, jnp.int32))
        U, _, _, _, its, _, ok = jax.lax.while_loop(cond, body, state0)
        uo_ref[...] = U
        itso_ref[...] = its
        broke_ref[...] = 1 - ok

    return kernel


@functools.lru_cache(maxsize=64)
def _make_solve(B: int, nely: int, lanes: int, tol: float, max_iter: int,
                interpret: bool):
    """Build (and cache) the fused-solve pallas_call for one
    (batch, mesh, tolerance) family, so serving engines share one
    compiled artifact per configuration."""
    f32 = jnp.float32
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _make_solve_kernel(nely, lanes, tol, max_iter),
        in_specs=[smem, smem] + [vmem] * 9,
        out_specs=[vmem, vmem, vmem],
        out_shape=[jax.ShapeDtypeStruct((B, lanes), f32),      # U
                   jax.ShapeDtypeStruct((B, 1), jnp.int32),    # its
                   jax.ShapeDtypeStruct((B, 1), jnp.int32)],   # broke
        interpret=interpret,
        name="cg_fused",
    )


def _anchor_layout(grid, nelx: int, nely: int, lanes: int):
    """(B, nelx, nely) element values -> (B, lanes) with element (ex, ey)
    on its anchor lane 2*(ex*(nely+1) + ey) and zeros elsewhere."""
    B = grid.shape[0]
    g = jnp.pad(grid, ((0, 0), (0, 0), (0, 1)))          # ey = nely: none
    g = jnp.stack([g, jnp.zeros_like(g)], axis=-1)       # odd lanes: none
    g = g.reshape(B, -1)
    return jnp.pad(g, ((0, 0), (0, lanes - g.shape[1])))


def solve_b_fused(bp: "fea2d.BatchProblem", X, tol: float = 1e-6,
                  max_iter: int = 2000, need=None, *,
                  interpret: Optional[bool] = None):
    """Batched Jacobi-PCG as ONE pallas_call: setup (loads, Jacobi
    diagonal, initial residual, lane layout) runs as regular XLA ops,
    then the whole convergence loop executes inside a single kernel
    launch with the krylov state VMEM-resident throughout. Drop-in for
    ``fea2d.solve_b`` (same (U, iters, broke) return, same per-slot
    convergence and breakdown semantics, from zero) — reached via
    ``fea2d.solve_b(..., backend="fused")``.
    """
    # mesh dims from the density SHAPE (static), not bp fields — under
    # jit the BatchProblem's int leaves are tracers
    B, nely, nelx = X.shape
    ndof = 2 * (nelx + 1) * (nely + 1)
    lanes = _lanes(ndof)
    F = bp.f * bp.free_mask
    # loop invariants, computed ONCE: SIMP stiffness grid (for the
    # diagonal only — the kernel recomputes its own) + Jacobi diagonal
    e = fea2d._e_grid(bp, X)
    diag = fea2d._assemble(
        e[..., None] * jnp.diag(bp.KE)[None, None, None, :]).reshape(B, -1)
    diag = jnp.where(diag > 0, diag, 1.0)
    if need is None:
        need = jnp.ones((B,), bool)

    Z = F / diag * bp.free_mask
    RZ = fea2d.tree_dot(F, Z)
    fnorm = fea2d.tree_norm(F)
    pe = jnp.stack([jnp.asarray(bp.penal, jnp.float32),
                    jnp.asarray(bp.e_min, jnp.float32)])

    def row(v, fill=0.0):               # (B, ndof) -> (B, lanes)
        return jnp.pad(v.astype(jnp.float32), ((0, 0), (0, lanes - ndof)),
                       constant_values=fill)

    def col(v):                         # (B,) -> (B, 1)
        return v.astype(jnp.float32).reshape(B, 1)

    mask = (jnp.ones((B, nelx, nely), jnp.float32) if bp.elem_mask is None
            else bp.elem_mask.reshape(B, nelx, nely))
    solve = _make_solve(B, nely, lanes, float(tol), int(max_iter),
                        resolve_interpret(interpret))
    U, its, broke = solve(
        pe, bp.KE.astype(jnp.float32),
        _anchor_layout(X.reshape(B, nelx, nely), nelx, nely, lanes),
        _anchor_layout(mask, nelx, nely, lanes),
        row(diag, 1.0), row(bp.free_mask), col(need), col(fnorm),
        row(F), row(Z), col(RZ))
    return U[:, :ndof], its[:, 0], broke[:, 0] > 0
