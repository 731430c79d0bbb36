"""bench/metrics/host_stall_frac.py on a hand-made metrics registry: none
without the CPU-time counter, and the share of the host phases' wall
time their threads did not run with it."""
from types import SimpleNamespace

import pytest

from bench import span_reduce
from bench.run import bench_file, load_module
from repro.obs.metrics import MetricsRegistry, set_default_registry


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev = set_default_registry(reg)
    yield reg
    set_default_registry(prev)


def _read(ctx=None):
    return load_module(bench_file("metrics", "host_stall_frac.py"),
                       "test_metric_host_stall_frac").read(
        ctx or SimpleNamespace())


def test_none_without_the_cpu_counter(registry):
    """A program that keeps wall seconds only (the parent's counter,
    labelled by mesh and phase) reads as no metric, and nothing raises."""
    assert _read() is None
    host = registry.counter(span_reduce.HOST_SECONDS)
    for phase, t in (("harvest", 0.4), ("dispatch", 0.5), ("sync", 3.0)):
        host.inc(t, mesh="60x20", phase=phase)
    registry.counter(span_reduce.STEPS).inc(100, mesh="60x20")
    assert _read() is None


def test_share_of_host_time_not_running(registry):
    host = registry.counter(span_reduce.HOST_SECONDS)
    cpu = registry.counter("topo_host_cpu_seconds_total")
    # two shards; sync and wait are device waits and left out
    for shard, scale in ((0, 1.0), (1, 2.0)):
        for phase, wall_s, cpu_s in (("harvest", 0.4, 0.3),
                                     ("dispatch", 0.5, 0.25),
                                     ("admit", 0.1, 0.05),
                                     ("sync", 3.0, 0.01),
                                     ("wait", 5.0, 0.02)):
            host.inc(scale * wall_s, mesh="60x20", shard=shard, phase=phase)
            cpu.inc(scale * cpu_s, mesh="60x20", shard=shard, phase=phase)
    # 3 x (0.3 + 0.25 + 0.05) CPU s of 3 x (0.4 + 0.5 + 0.1) wall s
    assert _read() == pytest.approx(1.0 - 0.6 / 1.0)


def test_none_without_host_phases(registry):
    """CPU counter present, but the loops only waited on the device."""
    for name in (span_reduce.HOST_SECONDS, "topo_host_cpu_seconds_total"):
        registry.counter(name).inc(1.0, mesh="60x20", shard=0, phase="sync")
    assert _read() is None
