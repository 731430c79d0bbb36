"""FEA/SIMP baseline properties (unit + hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.fea import fea2d, simp


@pytest.fixture(scope="module")
def prob():
    return fea2d.mbb_problem(12, 6)


def test_stiffness_spd(prob):
    """u^T K u > 0 for nonzero free u (K SPD on free dofs)."""
    rng = np.random.default_rng(0)
    for _ in range(5):
        u = jnp.asarray(rng.standard_normal(prob.f.shape[0])) * prob.free_mask
        x = jnp.full((prob.nely, prob.nelx), 0.5)
        e = float(jnp.vdot(u, fea2d.stiffness_apply(prob, x, u)))
        assert e > 0


@settings(max_examples=10, deadline=None)
@given(st.floats(0.1, 1.0), st.floats(0.1, 1.0))
def test_stiffness_linearity(a, b):
    """K(x) (a u1 + b u2) == a K u1 + b K u2."""
    prob = fea2d.mbb_problem(8, 4)
    rng = np.random.default_rng(1)
    u1 = jnp.asarray(rng.standard_normal(prob.f.shape[0]))
    u2 = jnp.asarray(rng.standard_normal(prob.f.shape[0]))
    x = jnp.full((prob.nely, prob.nelx), 0.7)
    lhs = fea2d.stiffness_apply(prob, x, a * u1 + b * u2)
    rhs = a * fea2d.stiffness_apply(prob, x, u1) + b * fea2d.stiffness_apply(prob, x, u2)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), rtol=1e-4,
                               atol=1e-6)


def test_cg_solves(prob):
    x = jnp.full((prob.nely, prob.nelx), 0.5)
    u, it, _ = fea2d.solve(prob, x)
    r = prob.f * prob.free_mask - fea2d.stiffness_apply(prob, x, u)
    rel = float(jnp.linalg.norm(r) / jnp.linalg.norm(prob.f))
    assert rel < 5e-4      # fp32 CG floor on ill-conditioned SIMP stiffness
    assert int(it) < 2000


def test_fixed_dofs_zero(prob):
    x = jnp.full((prob.nely, prob.nelx), 0.5)
    u, _, _ = fea2d.solve(prob, x)
    fixed = np.where(np.asarray(prob.free_mask) == 0)[0]
    np.testing.assert_allclose(np.asarray(u)[fixed], 0.0)


def test_denser_is_stiffer(prob):
    """More material => lower compliance (monotonicity)."""
    u1, _, _ = fea2d.solve(prob, jnp.full((prob.nely, prob.nelx), 0.3))
    c1, _ = fea2d.compliance_and_sens(prob, jnp.full((prob.nely, prob.nelx), 0.3), u1)
    u2, _, _ = fea2d.solve(prob, jnp.full((prob.nely, prob.nelx), 0.9))
    c2, _ = fea2d.compliance_and_sens(prob, jnp.full((prob.nely, prob.nelx), 0.9), u2)
    assert float(c2) < float(c1)


def test_sensitivities_negative(prob):
    """dC/dx <= 0 everywhere: adding material never hurts compliance."""
    x = jnp.full((prob.nely, prob.nelx), 0.5)
    u, _, _ = fea2d.solve(prob, x)
    _, dc = fea2d.compliance_and_sens(prob, x, u)
    assert float(jnp.max(dc)) <= 1e-9


def test_simp_improves_and_respects_volume(prob):
    state, hist = simp.run_simp(prob, n_iter=8)
    assert hist["c"][-1] < hist["c"][0]
    assert abs(float(jnp.mean(state.x)) - prob.volfrac) < 0.01
    assert float(state.x.min()) >= 0.001 and float(state.x.max()) <= 1.0


def test_oc_update_volume_projection():
    x = jnp.full((6, 12), 0.5)
    dc = -jnp.abs(jax.random.normal(jax.random.key(0), (6, 12)))
    dv = jnp.ones_like(x) / x.size
    xn = simp.oc_update(x, dc, dv, 0.5)
    assert abs(float(jnp.mean(xn)) - 0.5) < 0.02


def test_pad_problem_passive_border_and_crop_roundtrip():
    p = fea2d.point_load_problem(10, 4, load_node=(3, 0), load=(0.0, -1.2))
    pp = fea2d.pad_problem(p, 12, 6)
    assert (pp.nelx, pp.nely) == (12, 6)
    m = np.asarray(pp.elem_mask)
    assert m.shape == (6, 12) and m.sum() == 10 * 4
    # mask follows the density-layout flat convention (el = ex*nely + ey)
    g = m.reshape(12, 6)
    assert g[:10, :4].all() and not g[10:, :].any() and not g[:, 4:].any()
    # crop_density inverts the embedding on an arbitrary design field
    rng = np.random.default_rng(0)
    x_orig = rng.random((4, 10)).astype(np.float32)
    buf = np.zeros((12, 6), np.float32)
    buf[:10, :4] = x_orig.reshape(10, 4)
    np.testing.assert_array_equal(
        fea2d.crop_density(buf.reshape(6, 12), 10, 4), x_orig)
    # exact fit: same problem back, just moved onto the masked family
    same = fea2d.pad_problem(p, 10, 4)
    assert np.asarray(same.elem_mask).all()
    np.testing.assert_array_equal(np.asarray(same.f), np.asarray(p.f))
    with pytest.raises(ValueError, match="smaller"):
        fea2d.pad_problem(p, 8, 4)
    with pytest.raises(ValueError, match="smaller"):
        fea2d.crop_density(buf.reshape(6, 12), 14, 4)


def test_padded_solve_matches_original_physics():
    """The passive border is inert: solving the padded problem at the
    embedded density gives the original compliance (padded elements have
    zero stiffness and their dofs are fixed, so the active subsystem is
    the original one)."""
    p = fea2d.point_load_problem(10, 4, load_node=(3, 0), load=(0.0, -1.2))
    pp = fea2d.pad_problem(p, 12, 6)
    xo = jnp.full((4, 10), p.volfrac)
    xp = jnp.asarray(np.asarray(pp.elem_mask) * p.volfrac)
    uo, _, _ = fea2d.solve(p, xo)
    up, _, _ = fea2d.solve(pp, xp)
    co, dco = fea2d.compliance_and_sens(p, xo, uo)
    cp, dcp = fea2d.compliance_and_sens(pp, xp, up)
    assert np.isclose(float(co), float(cp), rtol=1e-4)
    # sensitivities vanish identically on the passive border
    assert not np.asarray(dcp)[np.asarray(pp.elem_mask) == 0.0].any()


def test_masked_oc_update_freezes_passive_and_scales_volume():
    """With a mask the OC update keeps passive densities at exactly 0 and
    takes the volume constraint over ACTIVE elements only, so volfrac
    keeps its meaning on the original (pre-padding) mesh."""
    p = fea2d.point_load_problem(10, 4)
    mask = fea2d.pad_problem(p, 12, 6).elem_mask
    x = jnp.asarray(np.asarray(mask) * 0.5)
    dc = -jnp.abs(jax.random.normal(jax.random.key(1), (6, 12))) * mask
    dv = jnp.ones_like(x) / x.size
    xn = simp.oc_update(x, dc, dv, 0.5, mask=mask)
    m = np.asarray(mask)
    assert not np.asarray(xn)[m == 0.0].any()
    active_mean = float(np.asarray(xn)[m == 1.0].mean())
    assert abs(active_mean - 0.5) < 0.02


def test_padded_oc_volume_matches_dedicated():
    """Regression: the hybrid step used to hand ``oc_update_b`` the
    padded mesh's uniform volume gradient 1/(nelx*nely) even when
    ``bp.elem_mask`` marked most of it passive — the ACTIVE-element
    volume constraint has per-slot gradient 1/active_count under
    shape-class padding. After a step the active-region volume of a
    padded slot must equal the dedicated (unpadded) run's volume, and
    no NaNs may leak from the passive border (a masked dv of the form
    mask/active would put 0/0 on passive elements)."""
    from repro.fea import hybrid
    from repro.configs.cronet import get_cronet_config
    from repro.common import materialize
    from repro.core import cronet
    import dataclasses

    p = fea2d.point_load_problem(10, 4, load_node=(3, 0), load=(0.0, -1.2))
    pp = fea2d.pad_problem(p, 12, 6)

    def run(cfg_dims, probs):
        cfg = dataclasses.replace(get_cronet_config("small"),
                                  nelx=cfg_dims[0], nely=cfg_dims[1],
                                  hist_len=3)
        params = materialize(cronet.param_specs(
            dataclasses.replace(cfg, dtype="float32")), jax.random.key(0))
        bp = fea2d.stack_problems(probs)
        step = hybrid.make_hybrid_step(cfg, 50.0, precision="fp32")
        state = hybrid.init_state(cfg, bp)
        load_vol = fea2d.load_volume_b(bp)
        cparams = hybrid.cast_params(params, "fp32")
        for _ in range(3):
            state = step(cparams, bp, load_vol, state)
        return np.asarray(state.x)

    x_ded = run((10, 4), [p, p])
    x_pad = run((12, 6), [pp, pp])
    assert not np.isnan(x_pad).any(), "NaNs leaked from the passive border"
    m = np.asarray(pp.elem_mask)
    # passive border stays exactly empty
    assert not x_pad[0][m == 0.0].any()
    vol_ded = x_ded[0].mean()
    vol_pad = x_pad[0][m == 1.0].mean()
    assert abs(vol_pad - vol_ded) < 1e-3, (
        f"padded active volume {vol_pad:.6f} != dedicated {vol_ded:.6f}")
    # both runs actually project onto the volume constraint
    assert abs(vol_ded - p.volfrac) < 0.02


def test_load_volume_layout(prob):
    vol = fea2d.load_volume(prob)
    assert vol.shape == (4, prob.nely + 1, prob.nelx + 1, 1)
    # Fy at node (0,0) carries the unit load
    assert float(vol[1, 0, 0, 0]) == -1.0
    # left edge x-support flags set
    assert float(vol[2, :, 0, 0].sum()) == prob.nely + 1


def test_cg_breakdown_keeps_designs_finite_at_large_mesh():
    """Regression: on the published 60x20 mesh the f32 Jacobi-PCG of
    this load case stagnates in the 9th SIMP iteration, a search
    direction comes out with p.Kp <= 0, and the step used to overflow
    into NaN densities. A slot now stops at its last finite iterate and
    reports the breakdown — on the reference batched path, on the fused
    kernel (bitwise-equal under jit), and on the unbatched solve behind
    simp.run_simp — and the dataset leaves that target out."""
    from repro.fea import dataset as dsm

    case = dsm.sample_load_cases(2, seed=0)[1]
    prob = case.problem(60, 20)
    hist = dsm.run_simp_b([prob, fea2d.idle_problem(60, 20)], n_iter=10)[0]
    assert np.isfinite(hist["x"]).all() and np.isfinite(hist["c"]).all()
    assert hist["broke"][8]
    w, tg = dsm.window_trajectory(hist, 3)
    assert len(tg) == 10 - 3 - int(hist["broke"][3:].sum())

    bp = fea2d.stack_problems([prob, prob])
    X = jnp.asarray(np.stack([hist["x"][7]] * 2))
    solve = jax.jit(lambda b, x, backend: fea2d.solve_b(b, x, backend=backend),
                    static_argnums=2)
    ur, ir, br = solve(bp, X, "reference")
    uf, if_, bf = solve(bp, X, "fused")
    assert np.isfinite(np.asarray(ur)).all()
    np.testing.assert_array_equal(np.asarray(ur), np.asarray(uf))
    np.testing.assert_array_equal(np.asarray(ir), np.asarray(if_))
    np.testing.assert_array_equal(np.asarray(br), np.asarray(bf))
    assert np.asarray(br).all()

    _, ref = simp.run_simp(prob, n_iter=10)
    assert np.isfinite(ref["x"]).all() and np.isfinite(ref["c"]).all()


def test_window_trajectory_drops_broken_down_targets():
    """A target whose solve stopped at a CG breakdown is an unconverged
    iterate: build_dataset's windowing leaves it out, for every caller."""
    from repro.fea import dataset as dsm

    T, hist_len = 8, 3
    hist = {"x": np.arange(T, dtype=np.float32)[:, None, None]
                 * np.ones((T, 2, 3), np.float32),
            "u": np.arange(T, dtype=np.float32)[:, None]
                 * np.ones((T, 5), np.float32),
            "broke": np.isin(np.arange(T), [4, 6])}
    w, tg = dsm.window_trajectory(hist, hist_len)
    assert tg[:, 0].tolist() == [3, 5, 7]
    assert w.shape == (3, hist_len, 2, 3, 1)
    assert w[:, :, 0, 0, 0].tolist() == [[0, 1, 2], [2, 3, 4], [4, 5, 6]]
    hist["broke"][:] = True
    w, tg = dsm.window_trajectory(hist, hist_len)
    assert w.shape == (0, hist_len, 2, 3, 1) and tg.shape == (0, 5)
