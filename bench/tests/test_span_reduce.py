"""bench/span_reduce.py and the readers of its metrics, on hand-made events
and on a trace recorded on a TPU v5e (``data/v5e_span_sample.xplane.pb``,
written by ``record_span_sample.py``: the engine's fused-backend step at
30x10 with its ``topo.*`` spans, the last ticks of a wave of four
requests)."""
import os
import re
from types import SimpleNamespace

import numpy as np
import pytest

from bench import span_reduce, trace_reduce
from bench.run import load_module, bench_file
from repro.obs.metrics import MetricsRegistry, set_default_registry

DATA = os.path.join(os.path.dirname(__file__), "data")
SAMPLE = os.path.join(DATA, "v5e_span_sample.xplane.pb")
WITHOUT_SPANS = os.path.join(DATA, "v5e_sample.xplane.pb")
SAMPLE_TICKS = 5        # dispatching ticks in the recorded window
READERS = ("host_ms_per_tick", "lane_ops_ms_per_design", "cg_ms_per_tick",
           "forward_ms_per_tick")

CG = "%cg_fused.1 = (f32[8,4096]) custom-call(f32[8,4096] %pad.12)"
FWD = "%cronet_fused.3 = f32[8,1,2560] custom-call(f32[8,5,1408] %pad.4)"


def events():
    # window 1000..3000 ns. Device: the CG conditional 1100-1500 holds the
    # cg_fused kernel 1150-1450 and a setup op 1110-1140; the forward's
    # conditional 1580-1720 holds cronet_fused 1600-1700; a fusion
    # 1800-1900; an op cut by the window's end; one before it.
    dev = [("%cond.14 = (f32[8,2562]) conditional(s32[] %c)", 1100.0, 400.0),
           ("%pad.12 = f32[8,4096] pad(f32[8,2562] %x)", 1110.0, 30.0),
           (CG, 1150.0, 300.0),
           ("%cond.11.clone = (f32[8,2562]) conditional(s32[] %c)",
            1580.0, 140.0),
           (FWD, 1600.0, 100.0),
           ("%fusion.64 = f32[8,60,20] fusion(f32[8,60,20] %x)", 1800.0,
            100.0),
           ("%fusion.7 = f32[8] fusion(f32[8] %y)", 2950.0, 150.0),
           ("%fusion.1 = f32[8] fusion(f32[8] %y)", 100.0, 50.0)]
    # device idle: 1000-1100, 1500-1580, 1720-1800, 1900-2950 (1310 ns)
    shard = [
        ("topo.tick", 900.0, 110.0), ("topo.dispatch", 950.0, 10.0),
        ("topo.tick", 1020.0, 540.0),             # dispatches
        ("topo.harvest", 1020.0, 80.0),           # idle 80
        ("topo.seed", 1100.0, 100.0),             # busy
        ("topo.dispatch", 1200.0, 100.0),         # busy
        ("topo.upload", 1500.0, 60.0),            # idle 60
        ("topo.tick", 1560.0, 230.0),             # waits only
        ("topo.wait", 1560.0, 230.0),             # idle 20 + 70
        ("topo.tick", 1790.0, 710.0),             # dispatches
        ("topo.dispatch", 1790.0, 10.0),          # idle 10
        ("topo.sync", 1800.0, 700.0),             # idle 600
        ("topo.tick", 2900.0, 150.0),             # dispatches
        ("topo.admit", 2900.0, 90.0),             # idle 50
        ("topo.dispatch", 2990.0, 10.0),          # busy
    ]
    return {"host": {"bench-profiler": [("bench_window", 1000.0, 2000.0)],
                     "python3/12": shard},
            "devices": {"/device:TPU:0": dev}}


def test_region_of_kernel_names():
    assert span_reduce.region_of(CG) == "cg_solve"
    assert span_reduce.region_of("%cg_fused = f32[2] custom-call()") \
        == "cg_solve"
    assert span_reduce.region_of(FWD) == "cronet_forward"
    assert span_reduce.region_of("%cg_fused_x.1 = f32[2] copy()") is None
    assert span_reduce.region_of("%cond.14 = (f32[2]) conditional()") \
        is None


def test_attribution_counts_each_instant_once():
    out = span_reduce.reduce(events(), chips=1)
    assert out["window_s"] == pytest.approx(2000e-9)
    assert out["region_s"]["cg_solve"] == pytest.approx(300e-9)
    assert out["region_s"]["cronet_forward"] == pytest.approx(100e-9)
    assert out["region_ops"] == {"cg_solve": 1, "cronet_forward": 1}
    # the union, not the 1,120 ns the events sum to inside the window
    assert out["busy_s"] == pytest.approx(690e-9)
    assert out["rest_s"] == pytest.approx(290e-9)
    assert out["idle_s"] == pytest.approx(1310e-9)


def test_innermost_region_op_takes_the_instant():
    by_region, busy = span_reduce.attribute(
        [(0.0, 100.0, "a"), (50.0, 60.0, "b"), (90.0, 120.0, None)])
    assert by_region == {"a": pytest.approx(90.0), "b": pytest.approx(10.0)}
    assert busy == pytest.approx(120.0)


def test_ticks_that_dispatch_in_the_window():
    # the tick before the window and the tick that only waited do not count
    assert span_reduce.reduce(events(), chips=1)["ticks"] == 3


def test_idle_time_under_phases():
    out = span_reduce.reduce(events(), chips=1)
    assert out["idle_lane_ops_frac"] == pytest.approx(140 / 2000)
    assert out["idle_in_phases_frac"] == pytest.approx(890 / 1310)
    by_phase = out["idle_by_phase_s"]
    assert by_phase["wait"] == pytest.approx(90e-9)
    assert by_phase["sync"] == pytest.approx(600e-9)
    assert by_phase["seed"] == pytest.approx(0.0)


def _ctx(trace=None, steps=0, seconds=1.0):
    return SimpleNamespace(trace=trace, steps=steps, seconds=seconds)


def _read(name, ctx):
    return load_module(bench_file("metrics", name + ".py"),
                       "test_metric_" + name).read(ctx)


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev = set_default_registry(reg)
    yield reg
    set_default_registry(prev)


def test_breakdown_seconds_sums_the_kernels():
    ops = [[CG, 0.3], ["%cond.14 = (f32[8]) conditional()", 0.31],
           [FWD, 0.1], [FWD.replace("f32[8,", "f32[4,"), 0.05]]
    assert span_reduce.breakdown_seconds(ops) == {
        "cg_solve": pytest.approx(0.3),
        "cronet_forward": pytest.approx(0.15)}


def test_readers(registry):
    trace = trace_reduce.reduce(events(), chips=1)
    # 300 and 100 ns of kernel time in a 2000-ns span; 200 steps in a
    # 10-s window, so 200 / 10 * 2e-6 = 4e-5 ticks in the span
    ctx = _ctx(trace, steps=200, seconds=10.0)
    assert _read("cg_ms_per_tick", ctx) == pytest.approx(1e3 * 300e-9 / 4e-5)
    assert _read("forward_ms_per_tick", ctx) \
        == pytest.approx(1e3 * 100e-9 / 4e-5)
    host = registry.counter(span_reduce.HOST_SECONDS)
    for mesh, scale in (("60x20", 1.0), ("30x10", 1.0)):
        for phase, t in (("sync", 3.0), ("wait", 5.0), ("dispatch", 0.5),
                         ("harvest", 0.2), ("seed", 0.3), ("upload", 0.4),
                         ("admit", 0.1)):
            host.inc(scale * t, mesh=mesh, phase=phase)
    registry.counter(span_reduce.STEPS).inc(150, mesh="60x20")
    registry.counter(span_reduce.STEPS).inc(50, mesh="30x10")
    registry.counter("topo_completions_total").inc(20, mesh="60x20",
                                                  outcome="none")
    # 2 x (0.5 + 0.2 + 0.3 + 0.4 + 0.1) s over 200 steps
    assert _read("host_ms_per_tick", ctx) == pytest.approx(15.0)
    # 2 x (0.2 + 0.3 + 0.4) s over 20 designs
    assert _read("lane_ops_ms_per_design", ctx) == pytest.approx(90.0)


def test_readers_report_nothing_without_spans_or_counter(registry):
    """A program without the phase counter, the steps counter or the
    kernel names (a trace recorded before they existed; a registry with
    completions only) reads as no metric, and nothing raises."""
    trace = trace_reduce.reduce(trace_reduce.load(WITHOUT_SPANS), chips=1)
    assert span_reduce.breakdown_seconds(
        trace["breakdown"]["device_ops"]) == {}
    registry.counter("topo_completions_total").inc(5, mesh="60x20",
                                                  outcome="none")
    for ctx in (_ctx(trace, steps=100), _ctx(None, steps=100)):
        for name in READERS:
            assert _read(name, ctx) is None, name


# ------------------------------------------------------ recorded on a v5e


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.load(SAMPLE)


def _in_window(recorded):
    _, w0, w1 = trace_reduce._window(recorded["host"])
    ops = [(n, max(s, w0), min(s + d, w1))
           for n, s, d in recorded["devices"]["/device:TPU:0"]
           if s < w1 and s + d > w0]
    return w0, w1, ops


def test_recorded_trace_is_small():
    assert os.path.getsize(SAMPLE) <= 2 * 1024 * 1024


def test_recorded_regions_are_the_named_kernels(recorded):
    out = span_reduce.reduce(recorded, chips=1)
    _, _, ops = _in_window(recorded)
    for kernel, region in span_reduce.KERNEL_REGION.items():
        spans = [(s, e) for n, s, e in ops
                 if re.match(rf"%{kernel}(\.\d+)? = ", n)]
        assert spans, kernel
        assert out["region_ops"][region] == len(spans)
        assert out["region_s"][region] == pytest.approx(
            sum(e - s for s, e in spans) * 1e-9)
    # the CG conditional holds the CG kernel: its own events are longer
    conds = [e - s for n, s, e in ops if " conditional(" in n]
    assert sum(conds) * 1e-9 > out["region_s"]["cg_solve"]


def test_recorded_nested_events_count_once(recorded):
    out = span_reduce.reduce(recorded, chips=1)
    w0, w1, ops = _in_window(recorded)
    raw = sum(e - s for _, s, e in ops) * 1e-9
    assert out["busy_s"] < raw
    busy = trace_reduce.reduce(recorded, chips=1)["busy_s"]["/device:TPU:0"]
    assert out["busy_s"] == pytest.approx(busy)
    assert out["rest_s"] + sum(out["region_s"].values()) \
        == pytest.approx(out["busy_s"])
    assert out["idle_s"] + out["busy_s"] == pytest.approx((w1 - w0) * 1e-9)


def test_recorded_tick_markers(recorded):
    out = span_reduce.reduce(recorded, chips=1)
    _, w0, w1 = trace_reduce._window(recorded["host"])
    dispatches = [s for evs in recorded["host"].values()
                  for n, s, _ in evs
                  if n == span_reduce.DISPATCH and w0 <= s < w1]
    assert out["ticks"] == len(dispatches) == SAMPLE_TICKS


def test_recorded_idle_in_lane_ops_against_a_raster(recorded):
    """The share checked on a 100-ns grid of the window."""
    out = span_reduce.reduce(recorded, chips=1)
    w0, w1, ops = _in_window(recorded)
    grid = np.arange(w0, w1, 100.0) + 50.0
    busy = np.zeros(grid.shape, bool)
    for _, s, e in ops:
        busy[(grid >= s) & (grid < e)] = True
    lane = np.zeros(grid.shape, bool)
    names = {"topo." + p for p in span_reduce.LANE_OPS}
    for evs in recorded["host"].values():
        for n, s, d in evs:
            if n in names:
                lane[(grid >= s) & (grid < s + d)] = True
    share = float(np.mean(~busy & lane))
    assert share > 0
    assert out["idle_lane_ops_frac"] == pytest.approx(share, abs=2e-3)


def test_recorded_breakdown_holds_the_kernels_whole(recorded):
    """The readers' route: the kernels' time in ``trace_reduce``'s
    breakdown is their whole time in the span."""
    full = span_reduce.reduce(recorded, chips=1)["region_s"]
    ops = trace_reduce.reduce(recorded, chips=1)["breakdown"]["device_ops"]
    got = span_reduce.breakdown_seconds(ops)
    assert got.keys() == full.keys()
    for region, t in full.items():
        assert got[region] == pytest.approx(t)
