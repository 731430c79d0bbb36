"""Batched topology-optimization serving: bitwise slot-invariance vs
sequential runs, out-of-order slot refill, residual-gated FEA fallback."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import materialize
from repro.configs.cronet import get_cronet_config
from repro.core import cronet
from repro.fea import fea2d, hybrid
from repro.serve.topo_service import (TopoRequest, TopoServingEngine,
                                      auto_shards, shard_devices)
from repro.serve.types import EngineClosed, EngineState

U_SCALE = 50.0


@pytest.fixture(scope="module")
def cfg():
    # tiny mesh + short history: the full hybrid pipeline in seconds
    return dataclasses.replace(get_cronet_config("small"),
                               nelx=12, nely=4, hist_len=3)


@pytest.fixture(scope="module")
def params(cfg):
    return materialize(cronet.param_specs(
        dataclasses.replace(cfg, dtype="float32")), jax.random.key(0))


def _problems(n, nelx=12, nely=4):
    return [fea2d.point_load_problem(nelx, nely, load_node=(i % (nelx + 1), 0),
                                     load=(0.0, -1.0 - 0.1 * i))
            for i in range(n)]


# ----------------------------------------------------- batched == sequential


@pytest.mark.parametrize("error_threshold", [0.05, 1e9])
def test_batched_service_bitwise_equals_sequential(cfg, params,
                                                   error_threshold):
    """(a) The slot-batched engine must produce densities element-wise
    IDENTICAL (fp32 bitwise) to N standalone fea/hybrid.py runs — for both
    the FEA-fallback regime (tight threshold rejects the untrained net) and
    the surrogate-accepting regime (huge threshold exercises the CRONet
    decode path end to end)."""
    probs = _problems(5)
    seq = [hybrid.run_hybrid(cfg, params, u_scale=U_SCALE, n_iter=7,
                             precision="fp32", problem=p,
                             compute_metrics=False,
                             error_threshold=error_threshold)
           for p in probs]
    eng = TopoServingEngine(cfg, params, u_scale=U_SCALE, slots=3,
                            precision="fp32",
                            error_threshold=error_threshold)
    done = eng.run([TopoRequest(uid=i, problem=p, n_iter=7)
                    for i, p in enumerate(probs)])
    for r, s in zip(done, seq):
        assert r.done
        np.testing.assert_array_equal(r.density, s.density,
                                      err_msg=f"request {r.uid}")
        assert r.compliance == s.compliances[-1]
        assert r.cronet_iters == s.cronet_invocations
        assert r.fea_iters == s.fea_invocations
    if error_threshold > 1.0:
        # the accepting regime must actually accept some predictions,
        # otherwise the decode path was never compared
        assert all(r.cronet_iters > 0 for r in done)


# ------------------------------------------------------- out-of-order refill


def test_slot_refill_preserves_request_mapping(cfg, params):
    """(b) Heterogeneous n_iter means slots finish out of order and refill
    from the queue at different ticks; every uid must still get ITS OWN
    problem's result (bitwise vs a standalone run of that problem)."""
    probs = _problems(6)
    n_iters = [4, 9, 5, 8, 4, 6]     # finish order != submit order
    eng = TopoServingEngine(cfg, params, u_scale=U_SCALE, slots=2,
                            precision="fp32")
    reqs = [TopoRequest(uid=i, problem=p, n_iter=n)
            for i, (p, n) in enumerate(zip(probs, n_iters))]
    done = eng.run(reqs)
    assert all(r.done for r in done)
    for r in done:
        ref = hybrid.run_hybrid(cfg, params, u_scale=U_SCALE,
                                n_iter=r.n_iter, precision="fp32",
                                problem=probs[r.uid], compute_metrics=False)
        np.testing.assert_array_equal(r.density, ref.density,
                                      err_msg=f"request {r.uid}")
        assert r.fea_iters + r.cronet_iters == r.n_iter


# ------------------------------------------------------------- residual gate


def test_residual_gate_rejects_corrupted_prediction(cfg, params):
    """(c) A deliberately corrupted prediction (u_scale blown up 1e4x) must
    trip the residual gate: every post-warm-up iteration falls back to FEA
    and the design is exactly the pure-FEA-path design. Without the gate
    (threshold=inf) the corrupted surrogate IS accepted and wrecks the
    design — which is what makes the gate load-bearing."""
    prob = _problems(1)[0]
    n_iter = 8
    gated = hybrid.run_hybrid(cfg, params, u_scale=U_SCALE * 1e4,
                              n_iter=n_iter, precision="fp32", problem=prob,
                              compute_metrics=False, error_threshold=0.05)
    assert gated.cronet_invocations == 0
    assert gated.fea_invocations == n_iter
    # pure-FEA path: threshold 0 can never accept the surrogate
    fea_only = hybrid.run_hybrid(cfg, params, u_scale=U_SCALE * 1e4,
                                 n_iter=n_iter, precision="fp32",
                                 problem=prob, compute_metrics=False,
                                 error_threshold=0.0)
    np.testing.assert_array_equal(gated.density, fea_only.density)
    # control: gate disabled -> corrupted predictions are accepted
    ungated = hybrid.run_hybrid(cfg, params, u_scale=U_SCALE * 1e4,
                                n_iter=n_iter, precision="fp32",
                                problem=prob, compute_metrics=False,
                                error_threshold=float("inf"))
    assert ungated.cronet_invocations > 0
    assert not np.array_equal(ungated.density, gated.density)

    # same engine-level behaviour
    eng = TopoServingEngine(cfg, params, u_scale=U_SCALE * 1e4, slots=2,
                            precision="fp32", error_threshold=0.05)
    done = eng.run([TopoRequest(uid=0, problem=prob, n_iter=n_iter)])
    assert done[0].cronet_iters == 0
    assert done[0].fea_iters == n_iter
    np.testing.assert_array_equal(done[0].density, gated.density)


# ----------------------------------------------------------- batched FEA core


def test_solve_b_matches_single_solve(cfg):
    """Batched masked CG solves the same systems the single-problem CG
    solves (to CG tolerance; the two use different — each internally
    deterministic — reduction orders)."""
    probs = _problems(3)
    bp = fea2d.stack_problems(probs)
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.uniform(0.3, 0.9, (3, 4, 12)).astype(np.float32))
    U, its, _ = fea2d.solve_b(bp, X)
    for i, p in enumerate(probs):
        u_ref, _, _ = fea2d.solve(p, X[i])
        np.testing.assert_allclose(np.asarray(U[i]), np.asarray(u_ref),
                                   rtol=1e-3, atol=1e-5)
        # residual check: K u == f on free dofs (fp32 CG floor on SIMP
        # stiffness is ~5e-4, same as test_cg_solves)
        r = p.f * p.free_mask - fea2d.stiffness_apply(p, X[i], U[i])
        assert float(jnp.linalg.norm(r) / jnp.linalg.norm(p.f)) < 1e-3
    assert int(its.max()) < 2000


def test_idle_slot_costs_zero_cg_iterations(cfg):
    """An empty serving slot (idle_problem) converges instantly in the
    masked CG — padding must not burn solver iterations."""
    probs = [_problems(1)[0], fea2d.idle_problem(12, 4)]
    bp = fea2d.stack_problems(probs)
    X = jnp.full((2, 4, 12), 0.5)
    _, its, _ = fea2d.solve_b(bp, X)
    assert int(its[1]) == 0
    assert int(its[0]) > 0


def test_cg_breakdowns_reach_request_and_counter(cfg, params, monkeypatch):
    """A CG breakdown in an FEA fallback is counted per request and in
    ``topo_cg_breakdowns_total``, not mistaken for a converged solve."""
    from repro.obs import metrics as obs_metrics

    solve_b = fea2d.solve_b

    def always_broke(*args, **kwargs):
        U, its, broke = solve_b(*args, **kwargs)
        return U, its, jnp.ones_like(broke)

    monkeypatch.setattr(fea2d, "solve_b", always_broke)
    # an error_threshold no other test uses: a fresh (uncached) step
    reg = obs_metrics.MetricsRegistry()
    eng = TopoServingEngine(cfg, params, u_scale=U_SCALE, slots=2,
                            precision="fp32", error_threshold=0.0123,
                            metrics=reg)
    (r,) = eng.run([TopoRequest(uid=0, problem=_problems(1)[0], n_iter=5)])
    assert r.fea_iters == 5 and r.cg_breakdowns == 5
    assert reg.counter("topo_cg_breakdowns_total").value(mesh="12x4") == 5

    monkeypatch.setattr(fea2d, "solve_b", solve_b)
    eng = TopoServingEngine(cfg, params, u_scale=U_SCALE, slots=2,
                            precision="fp32", metrics=reg)
    (r,) = eng.run([TopoRequest(uid=1, problem=_problems(1)[0], n_iter=5)])
    assert r.cg_breakdowns == 0


def test_tree_sum_matches_sum():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((5, 130)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(fea2d.tree_sum(x, axis=-1)),
                               np.asarray(x).sum(axis=-1), rtol=1e-5,
                               atol=1e-5)
    # exact for the axis-padding edge cases
    for n in [1, 2, 3, 4, 7, 8]:
        y = jnp.arange(1.0, n + 1.0)
        assert float(fea2d.tree_sum(y)) == float(n * (n + 1) / 2)


# ------------------------------------------------------ shard device pinning


def test_shard_devices_is_the_single_pinning_source():
    """shard_devices() resolves the shard count AND pins devices in one
    place (the auto_shards/_Shard duplication flagged in PR 1): it is a
    pure function of (slots, shards, device list)."""
    fake = ["dev0", "dev1", "dev2"]
    assert shard_devices(8, devices=fake) == \
        ["dev" + str(i) for i in range(auto_shards(8, len(fake)))]
    # explicit shard count round-robins deterministically
    assert shard_devices(8, shards=2, devices=fake) == ["dev0", "dev1"]
    assert shard_devices(8, shards=1, devices=fake) == ["dev0"]
    # repeated resolution is identical (no hidden state)
    assert shard_devices(12, devices=fake) == shard_devices(12, devices=fake)
    with pytest.raises(ValueError):
        shard_devices(8, shards=3, devices=fake)   # 8 % 3 != 0
    with pytest.raises(ValueError):
        shard_devices(4, shards=4, devices=fake)   # width < 2
    with pytest.raises(ValueError):
        shard_devices(8, shards=4, devices=fake[:2])  # shards > devices
    with pytest.raises(ValueError):
        shard_devices(1, devices=fake)             # slots < 2


def test_shard_device_assignment_stable_across_restarts(cfg, params):
    """Engine restarts (and rebuilt engines with the same arguments) must
    pin the same shards to the same devices — the PR 1 regression risk of
    re-deriving placement per start."""
    eng = TopoServingEngine(cfg, params, u_scale=U_SCALE, slots=4,
                            precision="fp32")
    devs0 = [sh.device for sh in eng._shards]
    assert devs0 == shard_devices(4, eng.shards)
    probs = _problems(2)
    for _ in range(2):  # each run() starts and shuts down the tick loops
        eng.run([TopoRequest(uid=i, problem=p, n_iter=3)
                 for i, p in enumerate(probs)])
        assert [sh.device for sh in eng._shards] == devs0
    eng2 = TopoServingEngine(cfg, params, u_scale=U_SCALE, slots=4,
                             precision="fp32")
    assert [sh.device for sh in eng2._shards] == devs0


# ------------------------------------------------------ lifecycle machine


def test_engine_lifecycle_state_machine(cfg, params):
    """NEW -> RUNNING <-> STOPPED -> CLOSED: stop() is the restartable
    pause the run() shim cycles through; shutdown() is terminal and
    submit()/start() afterwards fail fast with EngineClosed instead of
    hanging or racing the tick loops."""
    probs = _problems(2)
    eng = TopoServingEngine(cfg, params, u_scale=U_SCALE, slots=2,
                            precision="fp32")
    assert eng.state is EngineState.NEW
    fut = eng.submit(TopoRequest(uid=0, problem=probs[0], n_iter=3))
    assert eng.state is EngineState.RUNNING
    assert fut.result(timeout=300).done
    eng.stop()
    assert eng.state is EngineState.STOPPED and not eng.running
    # STOPPED is restartable (run() depends on this)
    fut = eng.submit(TopoRequest(uid=1, problem=probs[1], n_iter=3))
    assert eng.state is EngineState.RUNNING
    assert fut.result(timeout=300).done
    eng.shutdown()
    assert eng.state is EngineState.CLOSED
    with pytest.raises(EngineClosed):
        eng.submit(TopoRequest(uid=2, problem=probs[0], n_iter=3))
    with pytest.raises(EngineClosed):
        eng.start()
    with pytest.raises(EngineClosed):
        eng.run([TopoRequest(uid=3, problem=probs[0], n_iter=3)])
    eng.shutdown()   # idempotent
    assert eng.state is EngineState.CLOSED


# --------------------------------------------------- completed-request ring


def test_completed_ring_buffer_evicts_oldest(cfg, params):
    """A long-lived engine must not grow its completed-request history
    without bound: completed_limit caps it, evicting oldest-first."""
    probs = _problems(4)
    eng = TopoServingEngine(cfg, params, u_scale=U_SCALE, slots=2,
                            precision="fp32", completed_limit=4)
    eng.run([TopoRequest(uid=i, problem=probs[i], n_iter=3)
             for i in range(4)])
    assert sorted(r.uid for r in eng._completed) == [0, 1, 2, 3]
    assert eng.throughput_stats()["requests"] == 4.0
    # a second full batch evicts the first one entirely, oldest-first
    eng.run([TopoRequest(uid=10 + i, problem=probs[i], n_iter=3)
             for i in range(4)])
    assert len(eng._completed) == 4
    assert sorted(r.uid for r in eng._completed) == [10, 11, 12, 13]
    # stats now cover only the surviving ring
    assert eng.throughput_stats()["requests"] == 4.0
    eng.shutdown()


def test_point_load_problem_default_is_mbb():
    a = fea2d.mbb_problem(12, 6)
    b = fea2d.point_load_problem(12, 6)
    np.testing.assert_array_equal(np.asarray(a.f), np.asarray(b.f))
    np.testing.assert_array_equal(np.asarray(a.free_mask),
                                  np.asarray(b.free_mask))
