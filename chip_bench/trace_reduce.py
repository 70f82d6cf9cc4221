"""Reduce a profiler trace (``.xplane.pb``) to device busy time, the top
device operations, and idle gaps labelled by the benchmark's host spans.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane. Host spans are the ``bench.*`` events the
harness writes with ``jax.profiler.TraceAnnotation``; the ``bench.window``
span bounds the traced stretch.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class TraceEvents:
    """Intervals in ns on the trace's clock."""

    device_ops: list  # (device, name, start_ns, end_ns)
    host_spans: list  # (name, start_ns, end_ns)


def find_xplane(directory: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load_events(path: str) -> TraceEvents:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                dev = int(m.group(1))
                ops.extend((dev, e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events)
            elif not m:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name.startswith("bench."))
    return TraceEvents(ops, spans)


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of merged ``busy`` intervals within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label_gap(gap, spans) -> str:
    """The innermost host span that overlaps the gap most, or ``host.other``."""
    best, best_key = "host.other", (0.0, 0.0)
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            continue
        overlap = min(e, gap[1]) - max(s, gap[0])
        key = (overlap, -(e - s))
        if overlap > 0 and key > best_key:
            best, best_key = name, key
    return best


def short_name(hlo_text: str) -> str:
    """``%fusion.63 = pred[...] fusion(...)`` -> ``fusion.63``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def leaves(ops) -> list:
    """The (start, end, name) operations that contain no other: a ``while``
    or ``conditional`` encloses the operations it runs, and counting both
    would count their time twice."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    out = []
    for i, (s, e, name) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt[0] >= e:
            out.append((s, e, name))
    return out


def reduce_events(ev: TraceEvents, n_devices: int, top: int = 10) -> dict | None:
    """busy_s (averaged over devices), window_s, the innermost device ops
    with the most time, and the longest idle gaps of device 0, each
    labelled by a host span. None
    when the trace holds no window span or no device operation in it."""
    windows = [(s, e) for name, s, e in ev.host_spans if name == WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = windows[0]
    busy_ns, per_op, gaps0 = 0.0, {}, []
    for dev in range(n_devices):
        ops = [(s, e, name) for d, name, s, e in ev.device_ops if d == dev]
        merged = union(clip([(s, e) for s, e, _ in ops], lo, hi))
        busy_ns += sum(e - s for s, e in merged)
        for s, e, name in leaves(ops):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                key = short_name(name)
                per_op[key] = per_op.get(key, 0.0) + (e - s)
        if dev == 0:
            gaps0 = gaps(merged, lo, hi)
    if not per_op:
        return None
    longest = sorted(gaps0, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_ns / n_devices / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, t / 1e9] for n, t in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label_gap(g, ev.host_spans), (g[1] - g[0]) / 1e9]
                      for g in longest],
    }
