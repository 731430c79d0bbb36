"""Fused batched-CG backend contracts (kernels/cg_fused.py).

The whole suite pins ONE invariant from three directions: the fused
solve (``fea2d.solve_b(..., backend="fused")`` — the entire Jacobi-PCG
loop inside a single pallas_call) is a pure deployment knob. Densities,
displacements, and per-slot iteration counts are BITWISE-equal to the
reference XLA path across batch widths, ``need`` masks, zero-load
slots and shape-class ``elem_mask`` padding; the serving engine on the fused
backend keeps the no-recompilation streaming contract; and every
kernel entry point resolves ``interpret=None`` by platform
auto-detection instead of hardwiring the interpreter.

Widths start at 2: the reference's bitwise slot-invariance only holds
for batch >= 2 (unit batch dims lower through different
vectorization), so the fused contract is defined on the same domain.

The sweeps compare UNDER JIT — the contract's domain (see the
cg_fused.py module docstring): the serving tick always runs jitted,
and two standalone eager programs are not bitwise-stable on CPU XLA
even reference-vs-reference (different FMA-contraction choices in the
``_ke_apply`` stencil chain).
"""
import dataclasses
import inspect
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import materialize
from repro.configs.cronet import get_cronet_config
from repro.core import cronet
from repro.fea import fea2d
from repro.kernels import resolve_interpret
from repro.serve.topo_service import TopoRequest, TopoServingEngine

U_SCALE = 50.0


def _probs(n, nelx=12, nely=4):
    return [fea2d.point_load_problem(
        nelx, nely, load_node=(i % (nelx - 1), 0),
        load=(0.05 * i, -1.0 - 0.1 * i)) for i in range(n)]


def _solve_both(bp, X, need=None):
    # jitted with (bp, X, ...) as traced arguments — the same calling
    # convention as the engine's compiled tick, the contract's domain
    ref = jax.jit(lambda b, x, n: fea2d.solve_b(b, x, need=n))(bp, X, need)
    fus = jax.jit(lambda b, x, n: fea2d.solve_b(b, x, need=n,
                                                backend="fused"))(bp, X, need)
    return ref, fus


def _assert_bitwise(ref, fus, msg):
    (ur, ir, br), (uf, if_, bf) = ref, fus
    np.testing.assert_array_equal(np.asarray(ur), np.asarray(uf),
                                  err_msg=f"{msg}: U diverged")
    np.testing.assert_array_equal(np.asarray(ir), np.asarray(if_),
                                  err_msg=f"{msg}: iteration counts diverged")
    np.testing.assert_array_equal(np.asarray(br), np.asarray(bf),
                                  err_msg=f"{msg}: breakdown flags diverged")


# --------------------------------------------------- bitwise equivalence


@pytest.mark.parametrize("width", [2, 3, 4])
def test_fused_bitwise_across_widths(width):
    """Cold-start solves at several batch widths: fused == reference
    bitwise, including identical per-slot iteration counts."""
    bp = fea2d.stack_problems(_probs(width))
    X = jnp.stack([jnp.full((4, 12), 0.3 + 0.1 * i) for i in range(width)])
    _assert_bitwise(*_solve_both(bp, X), msg=f"width {width}")


def test_fused_bitwise_warm_start_and_need_mask():
    """Partial ``need`` masks — the serving tick's actual calling
    convention, where every solve starts from zero (fea2d.solve_b) —
    stay bitwise. Slots with need=False come back zero, untouched."""
    bp = fea2d.stack_problems(_probs(3))
    X = jnp.stack([jnp.full((4, 12), 0.5)] * 3)
    need = jnp.asarray([True, False, True])
    ref, fus = _solve_both(bp, X, need=need)
    _assert_bitwise(ref, fus, msg="need mask")
    # the frozen slot stays at zero and burns zero iterations
    assert not np.asarray(ref[0][1]).any()
    assert int(ref[1][1]) == int(fus[1][1]) == 0
    assert int(ref[1][0]) > 0 and int(ref[1][2]) > 0


def test_fused_bitwise_under_elem_mask_padding():
    """Shape-class padded problems (passive border, elem_mask) solve
    bitwise-identically on the fused backend."""
    raw = [fea2d.point_load_problem(10, 4, load_node=(3 + i, 0),
                                    load=(0.0, -1.0 - 0.2 * i))
           for i in range(2)]
    bp = fea2d.stack_problems([fea2d.pad_problem(p, 12, 6) for p in raw])
    X = bp.elem_mask * 0.5
    _assert_bitwise(*_solve_both(bp, X), msg="elem_mask padding")


# ------------------------------------------ zero-load stall (regression)


def test_zero_load_slot_with_stale_warm_start_converges_immediately():
    """Regression: a slot with f == 0 (empty serving lane) used to burn
    max_iter iterations when a previous occupant's displacement was
    handed in as its start. Solves now start from zero, so R = F = 0
    meets ``rnorm > tol * fnorm`` at once, on BOTH backends."""
    live = _probs(1)[0]
    idle = live._replace(f=jnp.zeros_like(live.f))     # load-free lane
    bp = fea2d.stack_problems([live, idle])
    X = jnp.stack([jnp.full((4, 12), 0.5)] * 2)
    ref, fus = _solve_both(bp, X)
    _assert_bitwise(ref, fus, msg="zero-load slot")
    its = np.asarray(ref[1])
    assert its[1] == 0, f"idle slot burned {its[1]} iterations"
    assert 0 < its[0] < 2000, "live slot failed to converge"


def test_unknown_backend_raises():
    bp = fea2d.stack_problems(_probs(2))
    X = jnp.stack([jnp.full((4, 12), 0.5)] * 2)
    with pytest.raises(ValueError, match="backend"):
        fea2d.solve_b(bp, X, backend="magic")


# ------------------------------------------- interpret auto-detection


def test_resolve_interpret_auto_detects_platform():
    """None -> interpret exactly on CPU hosts; explicit bools win."""
    assert resolve_interpret(None) == (jax.default_backend() == "cpu")
    assert resolve_interpret() == resolve_interpret(None)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_kernel_entry_points_default_to_auto_detection():
    """Regression: kernel entry points used to hardwire interpret=True,
    silently running the Pallas interpreter on accelerator hosts. Every
    public entry's ``interpret`` default must now be None (auto),
    including the fusion-config dispatcher ``fusion.infer``."""
    from repro.core import fusion
    from repro.kernels import (cg_fused, conv, cronet_pipeline,
                               flash_attention, gemm, pool, silu, slstm)
    entries = [conv.conv2d, conv.conv3d, gemm.gemm, pool.maxpool2d,
               pool.adaptive_avg_pool2d, pool.adaptive_avg_pool3d,
               silu.silu_lut, silu.silu_exact, slstm.slstm_fused,
               flash_attention.flash_attention,
               flash_attention.flash_attention_causal_gqa,
               cronet_pipeline.cronet_fused, cg_fused.solve_b_fused,
               fusion.infer]
    for fn in entries:
        default = inspect.signature(fn).parameters["interpret"].default
        assert default is None, (
            f"{fn.__module__}.{fn.__name__} hardwires interpret="
            f"{default!r}; must default to None (platform auto-detect)")


# -------------------------------------- serving engine on the fused path


def test_fused_engine_bitwise_and_streaming_cache_hit():
    """End to end: an engine on fea_backend='fused' serves densities
    bitwise-equal to the reference engine, and live admission against
    its running tick loop never retraces the compiled step."""
    cfg = dataclasses.replace(get_cronet_config("small"),
                              nelx=12, nely=4, hist_len=3)
    params = materialize(cronet.param_specs(
        dataclasses.replace(cfg, dtype="float32")), jax.random.key(0))
    pool = _probs(4, nelx=cfg.nelx, nely=cfg.nely)
    reqs = [(i % len(pool), 3 + i % 3) for i in range(4)]

    dens = {}
    for fb in ("reference", "fused"):
        eng = TopoServingEngine(cfg, params, u_scale=U_SCALE, slots=2,
                                precision="fp32", fea_backend=fb)
        assert eng.throughput_stats()["fea_backend"] == fb
        # warm the width-2 step, then measure the streaming trace delta
        eng.run([TopoRequest(uid=100 + k, problem=pool[pi], n_iter=ni)
                 for k, (pi, ni) in enumerate(reqs[:2])])
        traces_warm = eng.step.trace_count[0]
        futs = []
        for k, (pi, ni) in enumerate(reqs):
            futs.append(eng.submit(
                TopoRequest(uid=k, problem=pool[pi], n_iter=ni)))
            time.sleep(0.01)
        done = [f.result(timeout=300) for f in futs]
        assert eng.drain(timeout=60)
        assert eng.step.trace_count[0] == traces_warm, \
            f"live admission retraced the {fb} step"
        eng.shutdown()
        dens[fb] = [np.asarray(r.density) for r in done]

    for i, (a, b) in enumerate(zip(dens["reference"], dens["fused"])):
        np.testing.assert_array_equal(
            a, b, err_msg=f"request {i}: fused-engine density diverged")
