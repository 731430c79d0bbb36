"""Observability-layer contracts (repro.obs):

  * histogram bucket estimates bracket the EXACT sample percentiles
    (property-based over random sample sets);
  * trace span timelines are contiguous by construction — phase
    durations sum exactly to end-to-end latency;
  * tracing is bitwise-invisible to serving, through forced
    preemption/park/restore cycles and canary routing;
  * telemetry snapshots tolerate torn trailing lines (crash mid-write)
    and enforce newest-N retention;
  * concurrent metric / fleet-event recording loses no updates
    (property-based thread interleavings);
  * ``FleetEvent.t_mono`` is populated everywhere and ``fleet_events``
    sorts on it; ``TopoRequest.admitted_t`` recovers queue age.
"""
import dataclasses
import json
import random
import threading
import time

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry, Trace,
                       TelemetrySnapshotter, default_registry,
                       exponential_buckets, read_snapshots,
                       set_default_registry)
from repro.obs import dashboard
from repro.obs import trace as obs_trace

U_SCALE = 50.0


# ------------------------------------------------------------- metrics


def test_exponential_buckets_strictly_increasing():
    b = exponential_buckets(1e-4, 2.0, 21)
    assert len(b) == 21
    assert all(b[i] < b[i + 1] for i in range(len(b) - 1))
    assert b[1] / b[0] == pytest.approx(2.0)
    with pytest.raises(ValueError):
        Histogram("h", buckets=[1.0, 1.0, 2.0])


def test_counter_labels_and_totals():
    c = Counter("reqs")
    c.inc()
    c.inc(2, mesh="12x4")
    c.inc(3, mesh="12x4")
    c.inc(mesh="10x6")
    assert c.value() == 1.0
    assert c.value(mesh="12x4") == 5.0
    assert c.total() == 7.0
    # label VALUES are stringified, so 4 and "4" are the same series
    c.inc(rung=4)
    c.inc(rung="4")
    assert c.value(rung=4) == 2.0


def test_gauge_callback_sampled_at_read_and_exception_safe():
    box = {"v": 3.0}
    g = Gauge("depth", callback=lambda: box["v"])
    assert g.value() == 3.0
    box["v"] = 7.0
    assert g.value() == 7.0          # sampled at read, not registration
    bad = Gauge("bad", callback=lambda: 1 / 0)
    assert np.isnan(bad.value())     # a broken hook must not raise
    s = Gauge("set")
    s.set(2.0, mesh="12x4")
    s.inc(1.0, mesh="12x4")
    assert s.value(mesh="12x4") == 3.0


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c1 = reg.counter("x", "help")
    assert reg.counter("x") is c1
    with pytest.raises(TypeError):
        reg.histogram("x")
    # default-registry swap is how tests/benchmarks isolate themselves
    prev = set_default_registry(reg)
    try:
        assert default_registry() is reg
    finally:
        set_default_registry(prev)
    assert default_registry() is prev


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_histogram_percentiles_bracket_exact_values(seed):
    """The bucket estimate must land inside the bucket CONTAINING the
    exact percentile — bucket-width accuracy is the contract (fixed
    buckets, no per-observation allocation), not exactness."""
    rng = random.Random(seed)
    h = Histogram("lat", buckets=exponential_buckets(1e-4, 2.0, 21))
    samples = [rng.lognormvariate(-4.0, 1.5) for _ in range(500)]
    for v in samples:
        h.observe(v)
    bounds = (0.0,) + h.bounds
    for q in (50.0, 90.0, 99.0):
        exact = float(np.percentile(samples, q,
                                    method="inverted_cdf"))
        est = h.percentile(q)
        # locate the bucket holding the exact value: est must be in it
        i = next(k for k in range(len(bounds) - 1)
                 if exact <= bounds[k + 1]) if exact <= bounds[-1] \
            else len(bounds) - 2
        lo, hi = bounds[i], bounds[i + 1]
        assert lo <= est <= hi, \
            (q, exact, est, lo, hi)


def test_histogram_aggregates_across_labelsets_without_labels():
    h = Histogram("t", buckets=[1.0, 10.0, 100.0])
    h.observe(0.5, n=3, mesh="a")
    h.observe(50.0, mesh="b")
    assert h.count() == 4 and h.count(mesh="a") == 3
    assert h.sum() == pytest.approx(51.5)
    assert h.percentile(50.0) <= 1.0       # 3 of 4 obs in first bucket
    assert h.percentile(99.0) > 10.0


def test_prometheus_exposition_cumulative_buckets():
    reg = MetricsRegistry()
    reg.counter("c_total", "a counter").inc(2, mesh="12x4")
    h = reg.histogram("h_s", "a histogram", buckets=[1.0, 10.0])
    h.observe(0.5)
    h.observe(5.0)
    h.observe(50.0)
    text = reg.to_prometheus()
    assert "# TYPE c_total counter" in text
    assert 'c_total{mesh="12x4"} 2' in text
    # le buckets are CUMULATIVE and +Inf equals _count
    assert 'h_s_bucket{le="1"} 1' in text
    assert 'h_s_bucket{le="10"} 2' in text
    assert 'h_s_bucket{le="+Inf"} 3' in text
    assert "h_s_count 3" in text
    # snapshot mirrors the same series
    snap = reg.snapshot()
    assert snap["c_total"]["kind"] == "counter"
    assert snap["h_s"]["kind"] == "histogram"


# --------------------------------------------------------------- traces


def test_trace_spans_tile_end_to_end_exactly():
    """begin() closes the open span at the SAME stamp, so the phases
    tile submit -> done with zero gap — sum equals e2e exactly, not
    within tolerance."""
    tr = Trace(uid=7)
    tr.begin(obs_trace.QUEUED, t=100.0)
    tr.begin(obs_trace.COMPUTE, t=101.5, lane=0)
    tr.begin(obs_trace.PARKED, t=103.0, iters_done=3)
    tr.begin(obs_trace.COMPUTE, t=110.0, lane=1)
    tr.finish(t=112.25, iters=6)
    assert tr.complete
    phases = tr.phase_durations()
    assert phases == {"queued": 1.5, "compute": 1.5 + 2.25,
                      "parked": 7.0}
    assert sum(phases.values()) == tr.end_to_end_s() == 12.25
    assert tr.total_s() == tr.end_to_end_s()
    assert tr.preemption_cycles() == 1
    d = tr.to_dict()
    assert d["complete"] and len(d["spans"]) == 4
    assert "compute" in tr.render()


def test_trace_bounded_spans_and_split_accounting():
    tr = Trace(uid=1, max_spans=4)
    for k in range(10):
        tr.begin("compute", t=float(k))
    tr.finish(t=10.0)
    assert len(tr.spans) == 4 and tr.dropped_spans == 6
    tr.window(1.0, 2, 1, 1, 30)
    tr.window(2.0, 3, 0, 3, 90)
    assert tr.cronet_split() == {"cronet_iters": 1, "fea_iters": 4,
                                 "cg_iters": 120}
    tr.tick(0.5, 4, 1)
    assert list(tr.ticks) == [(0.5, 4, 1)]


# ------------------------------------------------------------ exporters


def test_snapshotter_torn_line_tolerance_and_retention(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc(5)
    path = str(tmp_path / "telemetry.jsonl")
    snap = TelemetrySnapshotter(path, registry=reg, interval_s=60.0,
                                max_snapshots=3)
    for _ in range(5):
        snap.snapshot_once()
    with open(path) as f:
        assert len(f.readlines()) == 3       # newest-N retention
    # crash mid-append: a torn trailing line must not poison readers
    with open(path, "a") as f:
        f.write('{"t": 1.0, "metrics": {"c": {"kin')
    recs = read_snapshots(path)
    assert len(recs) == 3
    assert all(r["metrics"]["c"]["kind"] == "counter" for r in recs)
    # the prom file rides along
    with open(snap.prom_path) as f:
        assert "# TYPE c counter" in f.read()


def test_snapshotter_extra_hook_failure_is_recorded(tmp_path):
    snap = TelemetrySnapshotter(str(tmp_path / "t.jsonl"),
                                registry=MetricsRegistry(),
                                extra=lambda: 1 / 0)
    rec = snap.snapshot_once()
    assert "extra_error" in rec and "extra" not in rec


def test_snapshotter_stop_keeps_handle_while_daemon_is_wedged(tmp_path):
    """Regression: ``stop()`` used to clear ``self._thread`` even when
    the join timed out — a later ``start()`` then spawned a SECOND loop
    racing the wedged one onto the same files. The handle must survive
    a timed-out join (so start() stays a no-op) and clear only once the
    daemon really exited."""
    snap = TelemetrySnapshotter(str(tmp_path / "t.jsonl"),
                                registry=MetricsRegistry(),
                                interval_s=60.0)
    # clean path: the daemon honours the stop event within the join
    # window, the handle clears, and a restart is allowed
    snap.start()
    snap.stop(final_snapshot=False)
    assert snap._thread is None

    # wedged path: a thread that outlives join(timeout) — simulated by
    # a stub handle, exactly what stop() inspects — must be KEPT
    class _Wedged:
        def __init__(self, alive):
            self.alive = alive
            self.joins = 0

        def join(self, timeout=None):
            self.joins += 1

        def is_alive(self):
            return self.alive

    wedged = _Wedged(alive=True)
    snap._stop.clear()
    snap._thread = wedged
    snap.stop(final_snapshot=True)
    assert snap._thread is wedged, "timed-out join must keep the handle"
    assert wedged.joins == 1
    # while the handle survives, start() cannot spawn a second loop
    assert snap.start() is snap
    assert snap._thread is wedged
    # the final snapshot still landed (snapshot_once serializes writes
    # under the instance lock, so it is safe beside a wedged loop)
    assert snap.snapshots_written >= 1
    # once the daemon actually died, the next stop() releases the handle
    wedged.alive = False
    snap.stop(final_snapshot=False)
    assert snap._thread is None


class _StringIO:
    def __init__(self):
        self.parts = []

    def write(self, s):
        self.parts.append(s)

    def flush(self):
        pass

    def getvalue(self):
        return "".join(self.parts)


def test_dashboard_renders_stats_and_instruments():
    reg = MetricsRegistry()
    reg.counter("topo_completions_total").inc(3, mesh="12x4")
    reg.histogram("topo_tick_latency_s").observe(0.01, mesh="12x4")
    stats = {"requests": 3.0, "problems_per_s": 1.5,
             "cronet_hit_rate": 0.5, "p99_latency_s": 0.2,
             "per_mesh": {"12x4": {"requests": 3.0,
                                   "cronet_hit_rate": 0.5,
                                   "p99_latency_s": 0.2,
                                   "model_tag": "prod"}}}
    frame = dashboard.render(registry=reg, stats=stats)
    assert "12x4" in frame and "topo_tick_latency_s" in frame
    out = _StringIO()
    dashboard.watch(registry=reg, stats_fn=lambda: stats,
                    interval_s=0.01, frames=2, out=out)
    assert out.getvalue().count("repro.obs dashboard") == 2


# -------------------------------------------- concurrent recording


@settings(max_examples=6, deadline=None)
@given(st.integers(2, 6),        # writer threads
       st.integers(0, 10 ** 6))  # interleaving seed
def test_concurrent_metric_recording_loses_nothing(n_threads, seed):
    """Counters/histograms take concurrent writers from every serving
    layer (shard loops, dispatcher, user threads): totals must be
    exact under arbitrary interleavings."""
    reg = MetricsRegistry()
    c = reg.counter("hits")
    h = reg.histogram("lat", buckets=exponential_buckets(1e-3, 4.0, 8))
    per = 200
    rng = random.Random(seed)
    stagger = [rng.random() * 1e-3 for _ in range(n_threads)]

    def work(k):
        time.sleep(stagger[k])
        for i in range(per):
            c.inc(mesh=f"m{k % 2}")
            h.observe(1e-3 * (i + 1), mesh=f"m{k % 2}")

    ts = [threading.Thread(target=work, args=(k,))
          for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.total() == n_threads * per
    assert h.count() == n_threads * per
    assert h.sum() == pytest.approx(
        n_threads * sum(1e-3 * (i + 1) for i in range(per)))


# ------------------------------------- serving integration (real engines)


@pytest.fixture(scope="module")
def trained():
    import jax

    from repro.common import materialize
    from repro.configs.cronet import get_cronet_config
    from repro.core import cronet

    cfg = dataclasses.replace(get_cronet_config("small"),
                              nelx=12, nely=4, hist_len=3)
    params = materialize(cronet.param_specs(
        dataclasses.replace(cfg, dtype="float32")), jax.random.key(0))
    return cfg, params


def _problems(n, nelx=12, nely=4):
    from repro.fea import fea2d
    return [fea2d.point_load_problem(nelx, nely,
                                     load_node=(i % (nelx - 1), 0),
                                     load=(0.0, -1.0 - 0.1 * i))
            for i in range(n)]


def test_tracing_bitwise_invisible_across_preemption(trained):
    """Force a park/restore cycle with tracing ON: densities stay
    bitwise-equal to the untraced run, the evicted request's trace
    carries a parked span, and every phase timeline tiles its measured
    end-to-end latency."""
    from repro.serve import TopoRequest, TopoServingEngine

    cfg, params = trained
    probs = _problems(3)

    def serve(trace_every):
        # tick_time_s pinned so the preemption decision is deterministic
        eng = TopoServingEngine(cfg, params, U_SCALE, slots=2,
                                precision="fp32", tick_time_s=10.0,
                                trace_every=trace_every)
        futs = [eng.submit(TopoRequest(uid=k, problem=probs[k],
                                       n_iter=10)) for k in range(2)]
        t0 = time.time()
        while any(a is None for a in eng._shards[0].slot_adm):
            assert time.time() - t0 < 60, "occupants never admitted"
            time.sleep(0.005)
        fut_u = eng.submit(TopoRequest(uid=9, problem=probs[2], n_iter=3),
                           deadline_s=35.0)
        done = [f.result(timeout=600) for f in futs]
        done.append(fut_u.result(timeout=600))
        traces = [eng.trace(r.uid) for r in done]
        parked = sum(r.preemptions for r in done)
        eng.shutdown()
        return done, traces, parked

    plain, none_traces, parked0 = serve(0)
    traced, traces, parked1 = serve(1)
    assert parked0 >= 1 and parked1 >= 1, "preemption never fired"
    assert all(t is None for t in none_traces)
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a.density, b.density,
                                      err_msg=f"uid {a.uid}")
    victim_spans = 0
    for r, tr in zip(traced, traces):
        assert tr is not None and tr.complete
        phases = tr.phase_durations()
        e2e = tr.end_to_end_s()
        assert abs(sum(phases.values()) - e2e) <= max(1e-6, 0.01 * e2e)
        # the span boundaries ARE the request's own stamps
        assert tr.submit_t == r.submit_t
        assert r.admitted_t is not None
        assert r.queue_wait_s == pytest.approx(r.admitted_t - r.submit_t)
        victim_spans += tr.preemption_cycles()
        assert tr.preemption_cycles() == r.preemptions
    assert victim_spans >= 1, "no trace recorded the park/restore cycle"


def test_tracing_bitwise_invisible_across_canary_routing(trained,
                                                         tmp_path):
    """Canary routing with tracing ON: the canary-vs-primary split and
    every density match a trace_every=0 gateway run of the same
    backlog; traces are registered at the gateway for BOTH tags."""
    from repro.serve import ModelRegistry, TopoGateway, TopoRequest

    cfg, params = trained
    reg = ModelRegistry(str(tmp_path))
    reg.register(params, cfg, U_SCALE, tag="prod")
    # same params under a distinct tag: routing must SPLIT tags while
    # densities stay comparable across the traced/untraced runs
    reg.register(params, cfg, U_SCALE, tag="cand")
    probs = _problems(4)

    def serve(trace_every):
        gw = TopoGateway.from_registry(reg, tag="prod", slots=2,
                                       trace_every=trace_every)
        warm = gw.submit(TopoRequest(uid=-1, problem=probs[0], n_iter=2))
        warm.result(timeout=600)
        gw.canary("cand", fraction=0.5, mesh=(12, 4),
                  auto_rollback=False)
        futs = [gw.submit(TopoRequest(uid=i, problem=p, n_iter=4))
                for i, p in enumerate(probs)]
        done = [f.result(timeout=600) for f in futs]
        traces = [gw.trace(r.uid) for r in done]
        events = gw.fleet_events()
        gw.shutdown()
        return done, traces, events

    plain, none_traces, _ = serve(0)
    traced, traces, events = serve(1)
    assert all(t is None for t in none_traces)
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a.density, b.density,
                                      err_msg=f"uid {a.uid}")
        assert a.routed_tag == b.routed_tag
    routed = {r.routed_tag for r in traced}
    assert len(routed) == 2, f"canary routing never split: {routed}"
    for r, tr in zip(traced, traces):
        assert tr is not None and tr.complete, f"uid {r.uid}"
        e2e = tr.end_to_end_s()
        assert abs(sum(tr.phase_durations().values()) - e2e) \
            <= max(1e-6, 0.01 * e2e)
    # FleetEvent.t_mono is populated and fleet_events sorts on it
    assert events and all(e.t_mono > 0.0 for e in events)
    assert [e.t_mono for e in events] == sorted(e.t_mono for e in events)
    assert any(e.kind == "canary-start" for e in events)


def test_tick_phases_tile_the_loop_and_the_profiler_is_invisible(
        trained, tmp_path):
    """An engine on the fused backends (interpret mode) with a ladder and a
    forced preemption, so that every tick phase runs: each phase's host
    seconds reach ``topo_host_seconds_total``, the phases sum to the tick
    loop's wall time within 5%, ``topo_steps_total`` counts every
    compiled step, each phase is a span in a profiler trace,
    and densities are bitwise-equal with the profiler on."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.serve import TopoRequest, TopoServingEngine
    from repro.serve.topo_service import PHASES

    cfg, params = trained
    probs = _problems(5)

    def serve():
        reg = MetricsRegistry()
        eng = TopoServingEngine(cfg, params, U_SCALE, slots=4, ladder=(2, 4),
                                precision="fp32", tick_time_s=10.0,
                                backend="megakernel", fea_backend="fused",
                                metrics=reg)
        ticks = []
        tick = eng._tick

        def timed_tick(shard):
            t0 = time.perf_counter()
            try:
                return tick(shard)
            finally:
                ticks.append((t0, time.perf_counter()))

        eng._tick = timed_tick
        futs = [eng.submit(TopoRequest(uid=k, problem=probs[k], n_iter=40))
                for k in range(4)]
        t0 = time.time()
        while any(a is None for a in eng._shards[0].slot_adm):
            assert time.time() - t0 < 120, "occupants never admitted"
            time.sleep(0.002)
        futs.append(eng.submit(TopoRequest(uid=9, problem=probs[4],
                                           n_iter=3), deadline_s=35.0))
        done = [f.result(timeout=600) for f in futs]
        time.sleep(0.3)          # idle ticks: the wait phase
        eng.shutdown()
        host = reg.counter("topo_host_seconds_total")
        phases = {p: host.value(mesh="12x4", shard=0, phase=p)
                  for p in PHASES}
        steps = reg.counter("topo_steps_total").value(mesh="12x4", shard=0)
        assert steps == eng.total_steps > 0, (steps, eng.total_steps)
        return done, phases, ticks[-1][1] - ticks[0][0]

    plain, phases, loop_s = serve()
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced, _, _ = serve()
    finally:
        jax.profiler.stop_trace()

    assert sum(r.preemptions for r in plain) >= 1, "preemption never fired"
    assert all(s > 0 for s in phases.values()), phases
    assert abs(sum(phases.values()) - loop_s) <= 0.05 * loop_s, \
        (phases, loop_s)
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a.density, b.density,
                                      err_msg=f"uid {a.uid}")
    names = set()
    for path in glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                names.update(e.name for e in line.events
                             if e.name.startswith("topo."))
    assert names >= {"topo.tick"} | {"topo." + p for p in PHASES}, names
