"""From a JAX profiler trace to the program's own spans: device time by
step region, ticks, and what the host did while the device sat idle.

It reads what ``bench/trace_reduce.py``'s ``load`` reads, inside the same
``bench_window`` span, on the cell's device planes.

- Regions. A device op belongs to a region of the hybrid step when its
  event is one of the step's two Pallas kernels: the trace names an op by
  its HLO instruction text (``%cg_fused.1 = (...) custom-call(...)``),
  which starts with the kernel's ``name=``, and carries no stat with the
  op's scope. ``cronet_fused`` is the ``cronet_forward`` region and
  ``cg_fused`` the ``cg_solve`` region; ops of neither kernel are the
  rest. Each instant of device time counts once: nested events (a
  conditional and the kernel inside it) double count, so an instant goes
  to the innermost region op that covers it, and busy time is the union
  of every op's interval.
- Ticks. The engine's shard loop wraps each tick in a ``topo.tick`` step
  span. A tick counts when it starts in the window and holds a
  ``topo.dispatch`` span (a tick that only waited for work dispatched no
  step).
- Idle time by phase. The device is idle where none of its ops runs. Each
  ``topo.<phase>`` span of the shard loop claims the idle time it covers.
  The lane bookkeeping phases are ``harvest``, ``park``, ``rung``,
  ``seed`` and ``upload``.

A trace of a program without these spans or kernel names reduces to zero
ticks and no region ops.

The harness deletes its trace before the metric readers run, so they
read what it keeps: ``trace_reduce``'s breakdown, whose ten longest ops
by name hold the two kernels (``breakdown_seconds``, ``ms_per_tick``),
and the program's counters ``topo_host_seconds_total`` and
``topo_steps_total`` in the process's metrics registry
(``phase_seconds``). ``reduce`` reads a trace on disk:
``tests/record_span_sample.py`` prints it, and a harness that reduces its
trace before deleting it would read the ticks and the idle time under
each phase from it.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import re

from bench import trace_reduce

KERNEL_REGION = {"cronet_fused": "cronet_forward", "cg_fused": "cg_solve"}
TICK = "topo.tick"
DISPATCH = "topo.dispatch"
PHASE_PREFIX = "topo."
LANE_OPS = ("harvest", "park", "rung", "seed", "upload")
DEVICE_WAITS = ("sync", "wait")
HOST_SECONDS = "topo_host_seconds_total"
STEPS = "topo_steps_total"
_KERNEL = re.compile(r"^%?(" + "|".join(KERNEL_REGION) + r")(\.\d+)?[ =]")


def region_of(op_name: str):
    """The step region of a device op's event name, or None."""
    m = _KERNEL.match(op_name)
    return KERNEL_REGION[m.group(1)] if m else None


def breakdown_seconds(device_ops) -> dict:
    """Seconds of each region in ``trace_reduce``'s ``device_ops`` (``[name,
    seconds]`` of the longest ops): the sum over the region's kernel ops,
    whose events never nest. A region without a kernel op in the list is
    absent."""
    out = {}
    for name, t in device_ops:
        region = region_of(name)
        if region is not None:
            out[region] = out.get(region, 0.0) + t
    return out


def ms_per_tick(ctx, region: str):
    """A metric reader's value: ``region``'s device ms in the traced span,
    summed over the cell's chips, per tick of a shard, the span holding
    ticks at the window's rate (compiled steps of every shard in the
    window over its seconds, times the span). None where the run was not
    traced, dispatched no step or its breakdown names no op of the
    region."""
    if ctx.trace is None or ctx.steps <= 0:
        return None
    s = breakdown_seconds(ctx.trace["breakdown"]["device_ops"]).get(region)
    if not s:
        return None
    return 1e3 * s / (ctx.steps / ctx.seconds * ctx.trace["window_s"])


def phase_seconds(registry) -> dict:
    """Seconds of each tick phase in ``registry``'s
    ``topo_host_seconds_total``, summed over meshes; empty for a program
    that keeps no such counter."""
    counter = registry.counter(HOST_SECONDS)
    out = {}
    for key in counter.labelsets():
        labels = dict(key)
        out[labels["phase"]] = (out.get(labels["phase"], 0.0)
                                + counter.value(**labels))
    return out


def _intersect(a, b) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def attribute(ops):
    """``ops``: ``[(start, end, region or None)]``. Returns ``({region:
    length}, busy length)``: every instant of the ops' union once, to the
    region of the latest-starting region op that covers it (the innermost
    of nested events)."""
    ops = sorted(ops)
    bounds = sorted({t for s, e, _ in ops for t in (s, e)})
    by_region = {}
    active = []            # (-start, end, region): region ops begun so far
    k = 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(ops) and ops[k][0] <= a:
            s, e, region = ops[k]
            if region is not None:
                heapq.heappush(active, (-s, e, region))
            k += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        if active:
            region = active[0][2]
            by_region[region] = by_region.get(region, 0.0) + (b - a)
    busy = sum(e - s for s, e in trace_reduce._union(
        (s, e) for s, e, _ in ops))
    return by_region, busy


def reduce(events: dict, chips: int) -> dict:
    """Region seconds summed over the cell's chips, dispatching ticks,
    and the idle share under each ``topo.*`` phase (mean over chips)."""
    _, w0, w1 = trace_reduce._window(events["host"])
    span = w1 - w0
    phases = {}            # phase -> its spans clipped to the window
    ticks = []
    dispatches = []
    for evs in events["host"].values():
        for name, s, d in evs:
            if not name.startswith(PHASE_PREFIX) or s >= w1 or s + d <= w0:
                continue
            if name == TICK:
                if s >= w0:
                    ticks.append((s, s + d))
            else:
                if name == DISPATCH:
                    dispatches.append(s)
                phases.setdefault(name[len(PHASE_PREFIX):], []).append(
                    (max(s, w0), min(s + d, w1)))
    dispatches.sort()
    n_ticks = 0
    for s, e in ticks:
        i = bisect.bisect_left(dispatches, s)
        n_ticks += i < len(dispatches) and dispatches[i] < e
    phases = {p: trace_reduce._union(iv) for p, iv in phases.items()}
    lane_ops = trace_reduce._union(
        iv for p in LANE_OPS for iv in phases.get(p, []))
    any_phase = trace_reduce._union(iv for ivs in phases.values()
                                    for iv in ivs)

    planes = sorted(events["devices"], key=lambda p: int(
        trace_reduce._DEVICE.match(p).group(1)))[:chips]
    regions = {r: 0.0 for r in KERNEL_REGION.values()}
    region_ops = {r: 0 for r in KERNEL_REGION.values()}
    busy_total = 0.0
    idle, idle_lane_ops, idle_in_phases = 0.0, 0.0, 0.0
    idle_by_phase = {p: 0.0 for p in phases}
    for p in planes:
        ops = []
        for name, s, d in events["devices"][p]:
            if s < w1 and s + d > w0:
                region = region_of(name)
                if region is not None:
                    region_ops[region] += 1
                ops.append((max(s, w0), min(s + d, w1), region))
        by_region, busy = attribute(ops)
        for r, t in by_region.items():
            regions[r] += t
        busy_total += busy
        gaps, prev = [], w0
        for s, e in trace_reduce._union((s, e) for s, e, _ in ops):
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if prev < w1:
            gaps.append((prev, w1))
        idle += sum(e - s for s, e in gaps)
        idle_lane_ops += _intersect(gaps, lane_ops)
        idle_in_phases += _intersect(gaps, any_phase)
        for ph, ivs in phases.items():
            idle_by_phase[ph] += _intersect(gaps, ivs)
    n = max(len(planes), 1)
    return {
        "window_s": span * 1e-9,
        "ticks": n_ticks,
        "region_s": {r: t * 1e-9 for r, t in regions.items()},
        "region_ops": region_ops,
        "busy_s": busy_total * 1e-9,
        "rest_s": (busy_total - sum(regions.values())) * 1e-9,
        "idle_s": idle * 1e-9 / n,
        "idle_lane_ops_frac": idle_lane_ops / (n * span),
        "idle_in_phases_frac": (idle_in_phases / idle) if idle else None,
        "idle_by_phase_s": {p: t * 1e-9 / n
                            for p, t in sorted(idle_by_phase.items())},
    }


def reduce_dir(logdir: str, chips: int) -> dict:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return reduce(trace_reduce.load(max(paths, key=os.path.getmtime)),
                  chips)
