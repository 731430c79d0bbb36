"""host_ms_per_tick: host time of the engine's tick loops outside device
waits, per compiled step of a shard, over the whole run (warm-up, window
and drain): ``topo_host_seconds_total`` over every phase but ``sync`` and
``wait``, in ms, over ``topo_steps_total``. Both are read from the
process's metrics registry once the gateway has shut down; each tick
loop flushes them as it exits."""
from bench import span_reduce


def read(ctx):
    from repro.obs.metrics import default_registry

    registry = default_registry()
    steps = registry.counter(span_reduce.STEPS).total()
    phases = span_reduce.phase_seconds(registry)
    if not phases or steps <= 0:
        return None
    host = sum(t for p, t in phases.items()
               if p not in span_reduce.DEVICE_WAITS)
    return 1e3 * host / steps
