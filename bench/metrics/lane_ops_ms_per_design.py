"""lane_ops_ms_per_design: host time of the engine's lane bookkeeping per
completed design, over the whole run (warm-up, window and drain):
``topo_host_seconds_total`` over the phases ``harvest``, ``park``,
``rung``, ``seed`` and ``upload``, in ms, over ``topo_completions_total``,
both read from the process's metrics registry once the gateway has shut
down."""
from bench import span_reduce


def read(ctx):
    from repro.obs.metrics import default_registry

    registry = default_registry()
    done = registry.counter("topo_completions_total").total()
    phases = span_reduce.phase_seconds(registry)
    if not phases or done <= 0:
        return None
    return 1e3 * sum(phases.get(p, 0.0) for p in span_reduce.LANE_OPS) \
        / done
