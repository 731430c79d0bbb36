"""The engine's harvest read: on a tick that harvests, one device-to-host
copy of every lane's results, sliced by each finished lane. Every
harvested request must carry exactly what six eager reads of the same
state return (density, compliance, surrogate / FEA / CG iteration counts,
CG breakdowns), the shape-class crop must still apply, and
``topo_harvest_copies_total`` must count one copy per harvesting tick,
not one per lane."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.common import materialize
from repro.configs.cronet import get_cronet_config
from repro.core import cronet
from repro.fea import fea2d
from repro.obs import metrics as obs_metrics
from repro.serve.topo_service import TopoRequest, TopoServingEngine

U_SCALE = 50.0
NELX, NELY = 12, 4


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(get_cronet_config("small"), nelx=NELX,
                               nely=NELY, hist_len=3)


@pytest.fixture(scope="module")
def params(cfg):
    return materialize(cronet.param_specs(
        dataclasses.replace(cfg, dtype="float32")), jax.random.key(0))


def _eager(state, lane):
    """The six eager reads the harvest replaced."""
    return (np.asarray(state.x[lane]), float(state.compliance[lane]),
            int(state.n_cronet[lane]), int(state.n_fea[lane]),
            int(state.cg_iters[lane]), int(state.cg_breakdowns[lane]))


def _request(uid, n_iter, padded):
    node = 1 + uid % (NELX - 5)
    load = (0.0, -0.6 - 0.15 * uid)
    if not padded:
        return TopoRequest(uid=uid, n_iter=n_iter,
                           problem=fea2d.point_load_problem(
                               NELX, NELY, load_node=(node, 0), load=load))
    # a smaller mesh padded onto the class mesh, cropped back at harvest
    small = fea2d.point_load_problem(NELX - 4, NELY - 2,
                                     load_node=(node, 0), load=load)
    req = TopoRequest(uid=uid, n_iter=n_iter,
                      problem=fea2d.pad_problem(small, NELX, NELY))
    req.orig_mesh = (NELX - 4, NELY - 2)
    return req


def _drive(cfg, params, n_iters, padded=False, ladder=None):
    """Serve one request per entry of ``n_iters``, all admitted on the
    first tick of a one-shard engine, driving ``_tick`` by hand. Returns
    the requests, each harvest's eager reads and width by uid, the ticks
    that harvested, and the copy counter's value."""
    reg = obs_metrics.MetricsRegistry()
    eng = TopoServingEngine(cfg, params, u_scale=U_SCALE,
                            slots=ladder[-1] if ladder else len(n_iters),
                            shards=1, precision="fp32", ladder=ladder,
                            shape_padded=padded, metrics=reg)
    eng.start = lambda: None     # no tick thread: the test drives _tick
    sh = eng._shards[0]
    sh.activate()
    harvest = eng._harvest_lane
    eager = {}

    def recording_harvest(shard, lane, now):
        eager[shard.slot_adm[lane].req.uid] = (_eager(shard.state, lane),
                                               shard.width)
        return harvest(shard, lane, now)

    eng._harvest_lane = recording_harvest
    reqs = [_request(uid, n, padded) for uid, n in enumerate(n_iters)]
    futs = [eng.submit(r) for r in reqs]
    done = reg.counter("topo_completions_total")
    harvesting_ticks = 0
    for _ in range(max(n_iters) + 2):
        before = done.total()
        assert eng._tick(sh)
        harvesting_ticks += done.total() > before
    assert all(f.result(timeout=1).done for f in futs)
    copies = reg.counter("topo_harvest_copies_total").value(
        mesh=f"{NELX}x{NELY}", shard=0)
    return reqs, eager, harvesting_ticks, copies


def _assert_bitwise(req, reads):
    x, compliance, cro, fea, cg, broke = reads
    if req.orig_mesh is not None:
        x = fea2d.crop_density(x, *req.orig_mesh)
    assert req.density.dtype == x.dtype == np.float32
    assert req.density.shape == x.shape
    assert req.density.tobytes() == x.tobytes()
    assert np.float64(req.compliance).tobytes() == \
        np.float64(compliance).tobytes()
    assert (req.cronet_iters, req.fea_iters, req.cg_iters,
            req.cg_breakdowns) == (cro, fea, cg, broke)
    assert req.fea_iters > 0 and req.cg_iters > 0


# case -> (first harvest's rung, shape-padded engine). Lanes 0 and 1
# finish together on the first harvest, the rest one tick apart, so the
# ladder shrinks through the lower rungs as they drain
_CASES = {"rung2": (2, False), "rung4": (4, False), "rung8": (8, False),
          "padded": (4, True)}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_harvest_equals_six_eager_reads(cfg, params, case):
    """Every harvested request of a ladder engine, at each rung and on a
    shape-padded engine, carries bit for bit what six eager reads of the
    state it was harvested from return (cropped back on the padded
    engine), and the copies are one per harvesting tick."""
    width, padded = _CASES[case]
    n_iters = [3, 3] + [4 + k for k in range(width - 2)]
    reqs, eager, ticks, copies = _drive(cfg, params, n_iters, padded,
                                        ladder=(2, 4, 8))
    assert eager[0][1] == eager[1][1] == width
    for req in reqs:
        _assert_bitwise(req, eager[req.uid][0])
    if padded:
        assert reqs[0].density.shape == (NELY - 2, NELX - 4)
    assert ticks == width - 1 and copies == ticks


# schedule -> iterations of each request on a four-lane engine
_SCHEDULES = {"together": [3, 3, 3, 3], "apart": [2, 3, 4, 5],
              "pairs": [2, 2, 4, 4]}


@pytest.mark.parametrize("schedule", sorted(_SCHEDULES))
def test_harvest_copies_one_per_harvesting_tick(cfg, params, schedule):
    """``topo_harvest_copies_total`` counts the ticks that harvested: one
    copy serves every lane finishing on that tick."""
    n_iters = _SCHEDULES[schedule]
    reqs, eager, ticks, copies = _drive(cfg, params, n_iters)
    assert ticks == len(set(n_iters))
    assert copies == ticks
    for req in reqs:
        _assert_bitwise(req, eager[req.uid][0])
