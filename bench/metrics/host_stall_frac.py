"""host_stall_frac: the share of the tick loops' host time in which a
shard's thread was not running, over the whole run (warm-up, window and
drain): 1 - ``topo_host_cpu_seconds_total`` / ``topo_host_seconds_total``,
each summed over every shard and every phase but ``sync`` and ``wait``.
Wall time a thread spends in a host phase without running is time it
waited for the interpreter lock, the queue's lock or a host transfer. Both
counters are read from the process's metrics registry once the gateway
has shut down; None for a program that keeps no CPU-time counter."""
from bench import span_reduce

CPU_SECONDS = "topo_host_cpu_seconds_total"


def _host_phases(counter) -> float:
    return sum(counter.value(**dict(key)) for key in counter.labelsets()
               if dict(key)["phase"] not in span_reduce.DEVICE_WAITS)


def read(ctx):
    from repro.obs.metrics import default_registry

    registry = default_registry()
    cpu = registry.counter(CPU_SECONDS)
    if not cpu.labelsets():
        return None
    wall = _host_phases(registry.counter(span_reduce.HOST_SECONDS))
    if wall <= 0:
        return None
    return 1.0 - _host_phases(cpu) / wall
