"""From a JAX profiler trace to the device's busy time and a breakdown.

The traced span is the host annotation ``bench_window`` that the harness
opens after the profiler has started and closes before it stops. On each
device plane (``/device:TPU:<n>``) the operations are the events of the
line ``XLA Ops`` (every line, where a plane has no such line). A device is
busy where at least one of its operations runs: busy time is the length
of the union of their intervals inside the span, and the idle share is
1 - busy / span.

The breakdown names the operations that took most device time, and the
longest idle gaps of the first device, each labelled with the host event
(any thread but the one holding the annotation) that overlaps it most.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
TOP = 10
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def load(path: str) -> dict:
    """Events of an ``.xplane.pb``: ``{"devices": {plane: [(name, start_ns,
    dur_ns)]}, "host": {line: [(name, start_ns, dur_ns)]}}``."""
    from jax.profiler import ProfileData

    out = {"devices": {}, "host": {}}
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE.match(plane.name)
        if m:
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            out["devices"][plane.name] = [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for ln in ops for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                out["host"].setdefault(ln.name, []).extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in ln.events)
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _window(host: dict):
    for line, evs in host.items():
        for name, s, d in evs:
            if name == WINDOW:
                return line, s, s + d
    raise ValueError(f"no {WINDOW!r} annotation in the trace")


def reduce(events: dict, chips: int) -> dict:
    """Busy seconds per device, the traced span and the breakdown."""
    line, w0, w1 = _window(events["host"])
    planes = sorted(events["devices"],
                    key=lambda p: int(_DEVICE.match(p).group(1)))[:chips]
    if not planes:
        raise ValueError("no device plane in the trace")
    busy, per_op, first = {}, {}, None
    for p in planes:
        clipped = [(name, max(s, w0), min(s + d, w1))
                   for name, s, d in events["devices"][p]
                   if s < w1 and s + d > w0]
        merged = _union((s, e) for _, s, e in clipped)
        busy[p] = sum(e - s for s, e in merged) * 1e-9
        for name, s, e in clipped:
            per_op[name] = per_op.get(name, 0.0) + (e - s) * 1e-9
        if first is None:
            first = merged
    gaps, prev = [], w0
    for s, e in first + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    host = [(name, s, s + d) for ln, evs in events["host"].items()
            if ln != line for name, s, d in evs if d > 0]
    return {
        "busy_s": busy,
        "window_s": (w1 - w0) * 1e-9,
        "breakdown": {
            "device_ops": [[n, t] for n, t in sorted(
                per_op.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[_label(host, a, b), (b - a) * 1e-9]
                          for a, b in gaps],
        },
    }


def _label(host, a, b) -> str:
    best, key = "no host event", (0.0, 0.0)
    for name, s, e in host:
        overlap = min(e, b) - max(s, a)
        if overlap > 0 and (overlap, -(e - s)) > key:
            best, key = name, (overlap, -(e - s))
    return best


def reduce_dir(logdir: str, chips: int) -> dict:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return reduce(load(max(paths, key=os.path.getmtime)), chips)
