"""Plain reference for the CRONet hybrid SIMP step, in straightforward jax.numpy.

It imports nothing of the system under test. It holds:

- the CRONet forward (paper Table I: a time-distributed CNN and RNN over
  the density history, a 3-D CNN over the load volume, their product),
  with plain XLA convolutions and matmuls;
- the weight generator the benchmark uses for the system and the
  reference alike (normal, std 1/sqrt(fan_in), from the seed);
- the MBB point-load problem of the 88-line SIMP code (bilinear quads,
  E0 = 1, nu = 0.3, penalty 3), Jacobi-preconditioned CG from zero with
  ``tol`` 1e-6 and ``max_iter`` 2000, and the sensitivity filter and
  optimality-criteria update.

The CG reduces in one fixed pairwise order (``tree_sum``), so the answer
does not depend on how many problems are stacked. Everything is meant to
be called under ``jax.default_matmul_precision("highest")``, and computes
in the dtype of its inputs: float32 for the reference, bfloat16 for the
correctness control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PENAL = 3.0
E_MIN = 1e-9
NU = 0.3
CG_TOL = 1e-6
CG_MAX_ITER = 2000
RMIN = 1.5
MOVE = 0.2


# ----------------------------------------------------------------- weights


def param_shapes(c: dict) -> dict:
    """Weight shapes of CRONet (paper Table I) from a configuration dict."""
    t_feat = c["t_pool"][0] * c["t_pool"][1] * c["t_pool"][2] * c["t_c2"]
    b_feat = c["b_pool"][0] * c["b_pool"][1] * c["b_c2"]
    return {
        "trunk": {
            "conv1": (2, 3, 3, 1, c["t_c1"]),
            "conv2": (1, 3, 3, c["t_c1"], c["t_c2"]),
            "fc1": (t_feat, c["mid"]),
            "fc2": (c["mid"], c["p"]),
        },
        "branch": {
            "conv1": (3, 3, 1, c["b_c1"]),
            "conv2": (3, 3, c["b_c1"], c["b_c2"]),
            "rnn_wx": (b_feat, c["rnn_hidden"]),
            "rnn_wh": (c["rnn_hidden"], c["rnn_hidden"]),
            "fc1": (c["rnn_hidden"], c["mid"]),
            "fc2": (c["mid"], c["p"]),
        },
    }


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also those past 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_params(c: dict, seed: int) -> dict:
    """float32 weights from the seed, made on the device in one jitted call."""
    shapes = param_shapes(c)
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple))

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            jax.random.normal(k, s, jnp.float32) / math.sqrt(s[0])
            for k, s in zip(keys, leaves)])

    return init(seed_key(seed))


def param_count(c: dict) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        param_shapes(c), is_leaf=lambda s: isinstance(s, tuple)))


# ----------------------------------------------------------------- forward


def _conv2d(x, w):
    return lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _conv3d(x, w, causal_depth: bool):
    kd, kh, kw = w.shape[:3]
    pad_d = (0, kd - 1) if causal_depth else (0, 0)
    return lax.conv_general_dilated(
        x, w, (1, 1, 1), (pad_d, (kh // 2, kh // 2), (kw // 2, kw // 2)),
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))


def _bounds(n_in: int, n_out: int):
    """Adaptive pooling windows, PyTorch's rule."""
    return [((i * n_in) // n_out, -(-((i + 1) * n_in) // n_out))
            for i in range(n_out)]


def _aap2d(x, out_hw):
    """(B, H, W, C) -> (B, oh, ow, C), adaptive average pooling."""
    rows = []
    for hs, he in _bounds(x.shape[1], out_hw[0]):
        rows.append(jnp.stack([jnp.mean(x[:, hs:he, ws:we, :], axis=(1, 2))
                               for ws, we in _bounds(x.shape[2], out_hw[1])],
                              axis=1))
    return jnp.stack(rows, axis=1)


def forward(c: dict, params: dict, load_vol, hist):
    """CRONet: load_vol (B, 4, ny+1, nx+1, 1), hist (B, T, ny, nx, 1) -> (B, p)."""
    t = params["trunk"]
    x = jax.nn.silu(_conv3d(load_vol, t["conv1"], True))
    x = jax.nn.silu(_conv3d(x, t["conv2"], False))
    od = c["t_pool"]
    x = jnp.stack([_aap2d(jnp.mean(x[:, ds:de], axis=1), od[1:])
                   for ds, de in _bounds(x.shape[1], od[0])], axis=1)
    x = jax.nn.silu(x.reshape(x.shape[0], -1) @ t["fc1"])
    trunk = x @ t["fc2"]

    b = params["branch"]
    n, steps = hist.shape[:2]
    y = hist.reshape(n * steps, *hist.shape[2:])
    y = jax.nn.silu(_conv2d(y, b["conv1"]))
    y = jax.nn.silu(_conv2d(y, b["conv2"]))
    hh, ww = (y.shape[1] // 2) * 2, (y.shape[2] // 2) * 2
    y = y[:, :hh, :ww].reshape(y.shape[0], hh // 2, 2, ww // 2, 2, y.shape[3])
    y = _aap2d(jnp.max(y, axis=(2, 4)), c["b_pool"])
    feats = y.reshape(n, steps, -1)
    h = jnp.zeros((n, c["rnn_hidden"]), feats.dtype)
    for i in range(steps):
        h = jnp.tanh(feats[:, i] @ b["rnn_wx"] + h @ b["rnn_wh"])
    branch = jax.nn.silu(h @ b["fc1"]) @ b["fc2"]
    return branch * trunk


def decode_to_dofs(c: dict, out):
    """(B, p) -> (B, ndof): reshape to (32, 40, 2), bilinear resize to the
    nodal grid, node n = x*(nely+1) + y with dofs [2n, 2n+1]."""
    n = out.shape[0]
    grid = jax.image.resize(out.reshape(n, 32, 40, 2),
                            (n, c["nely"] + 1, c["nelx"] + 1, 2), "bilinear")
    return jnp.transpose(grid, (0, 2, 1, 3)).reshape(n, -1)


# ----------------------------------------------------------------- physics


def element_stiffness() -> np.ndarray:
    nu = NU
    k = np.array([1 / 2 - nu / 6, 1 / 8 + nu / 8, -1 / 4 - nu / 12,
                  -1 / 8 + 3 * nu / 8, -1 / 4 + nu / 12, -1 / 8 - nu / 8,
                  nu / 6, 1 / 8 - 3 * nu / 8])
    order = [[0, 1, 2, 3, 4, 5, 6, 7], [1, 0, 7, 6, 5, 4, 3, 2],
             [2, 7, 0, 5, 6, 3, 4, 1], [3, 6, 5, 0, 7, 2, 1, 4],
             [4, 5, 6, 7, 0, 1, 2, 3], [5, 4, 3, 2, 1, 0, 7, 6],
             [6, 3, 4, 1, 2, 7, 0, 5], [7, 2, 1, 4, 3, 6, 5, 0]]
    return 1 / (1 - nu ** 2) * k[np.array(order)]


def point_load(nelx: int, nely: int, load_x: int, fy: float):
    """MBB supports (left edge x, bottom-right y) and a vertical point load
    on top-edge node (load_x, 0). Returns (f, free, fixed) as float32."""
    ndof = 2 * (nelx + 1) * (nely + 1)
    f = np.zeros(ndof, np.float32)
    f[2 * load_x * (nely + 1) + 1] = fy
    fixed = list(range(0, 2 * (nely + 1), 2)) + [ndof - 1]
    free = np.ones(ndof, np.float32)
    free[fixed] = 0.0
    return f * free, free, 1.0 - free


def load_volume(nelx: int, nely: int, f, fixed):
    """(B, 4, ny+1, nx+1, 1) trunk input: [Fx, Fy, support_x, support_y]."""
    n, nx, ny = f.shape[0], nelx + 1, nely + 1

    def grid(a):
        return jnp.swapaxes(a.reshape(n, nx, ny), 1, 2)

    vol = jnp.stack([grid(f[:, 0::2]), grid(f[:, 1::2]),
                     grid(fixed[:, 0::2]), grid(fixed[:, 1::2])], axis=1)
    return vol[..., None]


def tree_sum(x):
    """Sum over the last axis in one fixed pairwise order."""
    n = x.shape[-1]
    p = 1 << max(n - 1, 0).bit_length()
    if p != n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, p - n)])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def _ke_apply(ke, ue):
    acc = ue[..., 0:1] * ke[:, 0]
    for j in range(1, 8):
        acc = acc + ue[..., j:j + 1] * ke[:, j]
    return acc


def _element_dofs(ug):
    """(B, nx+1, ny+1, 2) nodal grid -> (B, nx, ny, 8) element dofs."""
    return jnp.concatenate([ug[:, :-1, :-1], ug[:, 1:, :-1],
                            ug[:, 1:, 1:], ug[:, :-1, 1:]], axis=-1)


def _assemble(fe):
    """(B, nx, ny, 8) element forces -> (B, nx+1, ny+1, 2) nodal forces."""
    z = ((0, 0),)
    return ((jnp.pad(fe[..., 0:2], (*z, (0, 1), (0, 1), *z))
             + jnp.pad(fe[..., 2:4], (*z, (1, 0), (0, 1), *z)))
            + (jnp.pad(fe[..., 4:6], (*z, (1, 0), (1, 0), *z))
               + jnp.pad(fe[..., 6:8], (*z, (0, 1), (1, 0), *z))))


def _stiffness(x, penal, e_min):
    """(B, nely, nelx) densities -> (B, nelx, nely) SIMP moduli."""
    n, nely, nelx = x.shape
    return e_min + (x.reshape(n, nelx, nely) ** penal) * (1 - e_min)


def solve(x, f, free, penal, e_min):
    """Jacobi-preconditioned CG from zero, each problem to its own
    tolerance; returns (u, iterations, broke): ``broke`` where a search
    direction lost its curvature (p.Kp <= 0) and the solve stopped there."""
    n, nely, nelx = x.shape
    ke = jnp.asarray(element_stiffness(), x.dtype)
    e = _stiffness(x, penal, e_min)[..., None]

    def apply_k(p):
        ug = p.reshape(n, nelx + 1, nely + 1, 2)
        return _assemble(e * _ke_apply(ke, _element_dofs(ug))).reshape(
            n, -1) * free

    diag = _assemble(e * jnp.diag(ke)[None, None, None, :]).reshape(n, -1)
    diag = jnp.where(diag > 0, diag, 1.0)
    r = f * free
    z = r / diag * free
    rz = tree_sum(r * z)
    fnorm = jnp.sqrt(tree_sum(r * r))

    def active(r, its, ok):
        return ok & (jnp.sqrt(tree_sum(r * r)) > CG_TOL * fnorm) & (
            its < CG_MAX_ITER)

    def body(s):
        u, r, p, rz, its, ok = s
        kp = apply_k(p)
        pkp = tree_sum(p * kp)
        act = active(r, its, ok)
        good = pkp > 0
        ok = ok & (good | ~act)
        act = act & good
        alpha = rz / jnp.maximum(pkp, 1e-30)
        u_n = u + alpha[:, None] * p
        r_n = r - alpha[:, None] * kp
        z = r_n / diag * free
        rz_n = tree_sum(r_n * z)
        p_n = z + (rz_n / jnp.maximum(rz, 1e-30))[:, None] * p
        m = act[:, None]
        return (jnp.where(m, u_n, u), jnp.where(m, r_n, r),
                jnp.where(m, p_n, p), jnp.where(act, rz_n, rz),
                its + act.astype(jnp.int32), ok)

    u, _, _, _, its, ok = lax.while_loop(
        lambda s: jnp.any(active(s[1], s[4], s[5])), body,
        (jnp.zeros_like(r), r, z, rz, jnp.zeros((n,), jnp.int32),
         jnp.ones((n,), bool)))
    return u, its, ~ok


def compliance_and_sens(x, u, penal, e_min):
    n, nely, nelx = x.shape
    ke = jnp.asarray(element_stiffness(), x.dtype)
    ue = _element_dofs(u.reshape(n, nelx + 1, nely + 1, 2))
    ce = tree_sum(ue * _ke_apply(ke, ue)).reshape(n, -1)
    xf = x.reshape(n, -1)
    c = tree_sum((e_min + (xf ** penal) * (1 - e_min)) * ce)
    dc = -penal * xf ** (penal - 1) * (1 - e_min) * ce
    return c, dc.reshape(x.shape)


def sensitivity_filter(x, dc):
    """dc~ = conv(w * x * dc) / (x * conv(w)), w = max(0, rmin - dist)."""
    r = int(np.ceil(RMIN)) - 1
    wy, wx = np.meshgrid(np.arange(-r, r + 1), np.arange(-r, r + 1),
                         indexing="ij")
    w = jnp.asarray(np.maximum(0.0, RMIN - np.sqrt(wx ** 2 + wy ** 2))[
        ..., None, None], x.dtype)

    def conv(a):
        return lax.conv_general_dilated(
            a[..., None], w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))[..., 0]

    return conv(x * dc) / jnp.maximum(conv(jnp.ones_like(x))
                                      * jnp.maximum(x, 1e-3), 1e-9)


def oc_update(x, dc, volfrac):
    """Optimality criteria with 60 bisection steps on the multiplier."""
    n = x.shape[0]
    dv = 1.0 / (x.shape[1] * x.shape[2])

    def xnew(lmid):
        be = jnp.sqrt(jnp.maximum(-dc / (dv * lmid[:, None, None]), 1e-30))
        xn = jnp.clip(x * be, x - MOVE, x + MOVE)
        return jnp.clip(xn, 0.001, 1.0)

    def body(lims, _):
        l1, l2 = lims
        lmid = 0.5 * (l1 + l2)
        vol = tree_sum(xnew(lmid).reshape(n, -1)) / (x.shape[1] * x.shape[2])
        big = vol > volfrac
        return (jnp.where(big, lmid, l1), jnp.where(big, l2, lmid)), None

    (l1, l2), _ = lax.scan(body, (jnp.full((n,), 1e-9, x.dtype),
                                  jnp.full((n,), 1e9, x.dtype)),
                           None, length=60)
    return xnew(0.5 * (l1 + l2))


def fea_step(penal, e_min, volfrac, f, free, x_prev):
    """One FEA iteration of SIMP: the CG solve of x_prev (B, nely, nelx),
    its compliance, and the filtered optimality-criteria update. Returns
    (displacement, compliance, next design, CG iterations, breakdown)."""
    u, its, broke = solve(x_prev, f, free, penal, e_min)
    comp, dc = compliance_and_sens(x_prev, u, penal, e_min)
    x_next = oc_update(x_prev, sensitivity_filter(x_prev, dc), volfrac)
    return u, comp, x_next, its, broke


def last_step(c: dict, params: dict, u_scale: float, penal, e_min, volfrac,
              f, free, fixed, hist, x_prev):
    """Replay a request's last hybrid iteration from the densities it saw.

    hist (B, T, nely, nelx): the density history the surrogate was given
    at that iteration; x_prev (B, nely, nelx): the design the FEA solved.
    ``penal`` and ``e_min`` come in as arrays, so that the SIMP power is
    computed for a general exponent, as a solver that takes the penalty as
    a parameter computes it. Returns (relative error of the surrogate
    against the FEA displacement, compliance of x_prev, the next design,
    CG iterations of the solve, whether it broke down)."""
    lv = load_volume(c["nelx"], c["nely"], f, fixed)
    pred = decode_to_dofs(c, forward(c, params, lv, hist[..., None]))
    pred = pred * u_scale * free
    u, comp, x_next, its, broke = fea_step(penal, e_min, volfrac, f, free,
                                           x_prev)
    err = jnp.sqrt(tree_sum((pred - u) ** 2)) / jnp.maximum(
        jnp.sqrt(tree_sum(u * u)), 1e-30)
    return err, comp, x_next, its, broke
