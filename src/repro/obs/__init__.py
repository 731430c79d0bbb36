"""repro.obs — observability for the serving stack (stdlib, numpy and,
for profiler spans, ``jax.profiler``).

Three layers, one data path:

  * ``trace`` — per-request ``Trace`` span timelines (queued → compute
    → parked cycles → completion) with per-tick rings and the
    CRONet-accepted vs CG-fallback split, sampled via ``trace_every=N``
    on the engine/gateway and assembled lock-free on the tick path; and
    ``Phase``, a named host phase of a loop written into the JAX
    profiler's trace and summed, as wall and CPU time, for metrics
    counters.
  * ``metrics`` — process-wide ``MetricsRegistry`` of counters, gauges
    and fixed-exponential-bucket histograms (no per-observation
    allocation); every serving layer records into ``default_registry()``
    and every stats view/exporter reads from it.
  * ``export`` / ``dashboard`` — ``TelemetrySnapshotter`` (bounded
    atomic-replace JSONL + Prometheus text file) and the
    ``--observe`` live terminal renderer.

The structural contract, enforced by tests and the ``--observe``
benchmark: observability is bitwise-invisible (densities identical with
tracing on or off — recording is host-side stamps only, never device
work) and cheap (tracing+metrics overhead gated < 5% of tick latency).
"""
from repro.obs.dashboard import render, watch
from repro.obs.export import TelemetrySnapshotter, read_snapshots
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               default_registry, exponential_buckets,
                               set_default_registry)
from repro.obs.trace import Phase, Span, Trace

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "default_registry", "set_default_registry", "exponential_buckets",
    "Phase", "Span", "Trace",
    "TelemetrySnapshotter", "read_snapshots",
    "render", "watch",
]
