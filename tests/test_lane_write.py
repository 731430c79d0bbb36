"""The engine's lane write: one compiled program per dirty tick uploads the
slot constants, computes the TrunkNet inputs and re-seeds the flagged
lanes. It must hand the step exactly what the eager path did (a
device-put ``BatchProblem``, ``fea2d.load_volume_b``, ``hybrid.reset_slot``
per lane), compile once per rung, and count its dispatches."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import materialize
from repro.configs.cronet import get_cronet_config
from repro.core import cronet
from repro.fea import fea2d, hybrid
from repro.obs import metrics as obs_metrics
from repro.serve import topo_service
from repro.serve.topo_service import (TopoFuture, TopoRequest,
                                      TopoServingEngine)

U_SCALE = 50.0


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(get_cronet_config("small"), hist_len=3)


@pytest.fixture(scope="module")
def params(cfg):
    return materialize(cronet.param_specs(
        dataclasses.replace(cfg, dtype="float32")), jax.random.key(0))


def _admission(uid, problem):
    req = TopoRequest(uid=uid, problem=problem, n_iter=4)
    return topo_service._Admission(req, TopoFuture(req))


def _random_state(cfg, width, seed):
    """A mid-trajectory state: every leaf off its reset value, so a reset
    lane and an untouched one both show."""
    rng = np.random.default_rng(seed)
    shape = (width, cfg.nely, cfg.nelx)
    ints = lambda: rng.integers(1, 50, width, dtype=np.int32)
    return hybrid.HybridState(
        x=rng.random(shape, np.float32),
        hist=rng.random((width, cfg.hist_len) + shape[1:], np.float32),
        it=ints(), err=rng.random(width, np.float32),
        n_cronet=ints(), n_fea=ints(),
        compliance=rng.random(width, np.float32),
        cg_iters=ints(), cg_breakdowns=ints())


def _assert_leaves_equal(got, want):
    got_leaves, got_def = jax.tree.flatten(got)
    want_leaves, want_def = jax.tree.flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        assert (g.shape, g.dtype, g.weak_type) == (w.shape, w.dtype,
                                                   w.weak_type)
        assert g.sharding == w.sharding
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# case -> (shape-padded engine, lanes admitted, lanes cleared)
_CASES = {
    "admission": (False, [1], []),
    "cleared": (False, [], [0]),
    "several": (False, [0, -1], [1]),
    "padded": (True, [0], [-1]),
}


@pytest.mark.parametrize("case", sorted(_CASES))
@pytest.mark.parametrize("width", [2, 4, 8])
def test_fused_lane_write_bitwise_equals_eager_path(cfg, params, width,
                                                    case):
    """A tick's fused lane write against the path it replaced: the same
    ``BatchProblem`` leaves (values, dtypes, weak types, placement), the
    same ``load_vol``, and the state ``reset_slot`` gives lane by lane."""
    padded, admitted, cleared = _CASES[case]
    eng = TopoServingEngine(cfg, params, u_scale=U_SCALE, slots=width,
                            shards=1, precision="fp32", shape_padded=padded,
                            metrics=obs_metrics.MetricsRegistry())
    sh = eng._shards[0]
    sh.activate()
    dev = sh.device

    def problem(node, volfrac):
        if not padded:
            return fea2d.point_load_problem(
                cfg.nelx, cfg.nely, load_node=(node, 0),
                load=(0.0, -1.0 - 0.1 * node), volfrac=volfrac)
        # a smaller mesh on the class mesh: a passive border to mask
        return fea2d.pad_problem(fea2d.point_load_problem(
            cfg.nelx - 4, cfg.nely - 2, load_node=(node, 0),
            volfrac=volfrac), cfg.nelx, cfg.nely)

    for lane in range(width):   # every lane occupied
        sh.fill(lane, _admission(lane, problem(lane + 1, 0.3 + 0.05 * lane)))
    state = _random_state(cfg, width, seed=width)
    sh.state = jax.device_put(state, dev)
    admitted = [lane % width for lane in admitted]
    cleared = [lane % width for lane in cleared
               if lane % width not in admitted]   # width 2: lanes overlap
    for lane in admitted:
        sh.fill(lane, _admission(100 + lane, problem(2 * lane + 5, 0.45)))
    for lane in cleared:
        sh.fill(lane, None)

    # the eager path: device-put BatchProblem, load_volume_b, reset_slot
    ref_bp = jax.device_put(fea2d.BatchProblem(
        nelx=cfg.nelx, nely=cfg.nely, edof=eng._edof, KE=eng._KE,
        f=jnp.asarray(sh.f), free_mask=jnp.asarray(sh.free),
        fixed_x_mask=jnp.asarray(sh.fixed_x),
        volfrac=jnp.asarray(sh.volfrac), penal=eng._penal,
        e_min=eng._e_min,
        elem_mask=jnp.asarray(sh.elem) if padded else None), dev)
    ref_lv = fea2d.load_volume_b(ref_bp)
    ref_state = jax.device_put(state, dev)
    for lane in admitted + cleared:
        mask = (jnp.asarray(sh.elem[lane])
                if padded and sh.slot_adm[lane] is not None else None)
        ref_state = hybrid.reset_slot(cfg, ref_state, lane,
                                      float(sh.volfrac[lane]), mask)

    sh._upload(sh.seed(admitted + cleared))
    _assert_leaves_equal(sh.bp, ref_bp)
    _assert_leaves_equal(sh.load_vol, ref_lv)
    _assert_leaves_equal(sh.state, ref_state)
    assert eng._m_resets.value(mesh=eng._mesh_label) == len(admitted
                                                            + cleared)


# --------------------------------------------------- compiles and counters


def _small():
    c = dataclasses.replace(get_cronet_config("small"), nelx=12, nely=4,
                            hist_len=3)
    p = materialize(cronet.param_specs(
        dataclasses.replace(c, dtype="float32")), jax.random.key(0))
    pool = [fea2d.point_load_problem(
        c.nelx, c.nely, load_node=(i % (c.nelx - 1), 0),
        load=(0.0, -1.0 - 0.1 * i)) for i in range(6)]
    return c, p, pool


def test_lane_write_is_a_compiled_cache_hit():
    """The lane-write program compiles once per rung: live admissions
    after warm-up never retrace it, and a ladder engine's serving across
    every rung traces it at most ``len(rungs)`` times, all at start."""
    c, p, pool = _small()
    eng = TopoServingEngine(c, p, u_scale=U_SCALE, slots=2,
                            precision="fp32",
                            metrics=obs_metrics.MetricsRegistry())
    traces = eng._lane_write.trace_count
    eng.run([TopoRequest(uid=100 + i, problem=pool[i], n_iter=3)
             for i in range(2)])
    traces_warm = traces[0]
    long_fut = eng.submit(TopoRequest(uid=0, problem=pool[0], n_iter=30))
    futs = [eng.submit(TopoRequest(uid=1 + k, problem=pool[k + 1],
                                   n_iter=4)) for k in range(4)]
    for f in futs + [long_fut]:
        assert f.result(timeout=300).done
    eng.shutdown()
    assert traces[0] == traces_warm, "live admission retraced the lane write"

    eng = TopoServingEngine(c, p, u_scale=U_SCALE, slots=4,
                            precision="fp32", ladder=(2, 4),
                            metrics=obs_metrics.MetricsRegistry())
    traces0 = traces[0]
    futs = [eng.submit(TopoRequest(uid=k, problem=pool[k], n_iter=4))
            for k in range(2)]
    [f.result(timeout=300) for f in futs]
    traces_started = traces[0]
    futs = [eng.submit(TopoRequest(uid=10 + k, problem=pool[k], n_iter=5))
            for k in range(4)]
    [f.result(timeout=300) for f in futs]
    stats = eng.throughput_stats()
    eng.shutdown()
    assert stats["ladder"]["rung_steps"]["4"] > 0
    assert traces[0] == traces_started, "the ladder warm-up missed a rung"
    assert traces[0] - traces0 <= len(eng.rungs)


def test_lane_write_counters_count_the_ticks():
    """Driven tick by tick: ``topo_lane_writes_total`` counts the
    activation and every dirty tick that dispatched, and
    ``topo_lanes_reset_total`` the admitted and cleared lanes those writes
    re-seeded."""
    c, p, pool = _small()
    reg = obs_metrics.MetricsRegistry()
    eng = TopoServingEngine(c, p, u_scale=U_SCALE, slots=2, shards=1,
                            precision="fp32", metrics=reg)
    eng.start = lambda: None     # no tick thread: the test drives _tick
    sh = eng._shards[0]
    sh.activate()
    writes = lambda: reg.counter("topo_lane_writes_total").value(
        mesh="12x4")
    resets = lambda: reg.counter("topo_lanes_reset_total").value(
        mesh="12x4")
    assert (writes(), resets()) == (1, 0)       # activation: no reset
    futs = [eng.submit(TopoRequest(uid=k, problem=pool[k], n_iter=2))
            for k in range(3)]
    expected = [
        (2, 2),   # tick 1: two admissions
        (2, 2),   # tick 2: clean, no write
        (3, 4),   # tick 3: both harvested, one admitted, one cleared
        (3, 4),   # tick 4: clean
        (3, 4),   # tick 5: harvest leaves the shard idle, no dispatch
    ]
    for k, want in enumerate(expected):
        assert eng._tick(sh)
        assert (writes(), resets()) == want, k + 1
    assert all(f.result(timeout=1).done for f in futs)
    assert eng.total_steps == 4
