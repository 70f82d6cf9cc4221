#!/usr/bin/env python3
"""The program's own host spans in a profiler trace, set against the device.

The served path records ``repro.*`` spans with
``jax.profiler.TraceAnnotation`` (``runtime/service.py``,
``runtime/admission.py``, ``runtime/dispatch.py``); they land on the host
plane of the ``.xplane.pb`` on the same clock as the device planes and the
harness's ``bench.*`` spans. This module reads both kinds and gives, within
the ``bench.window`` span:

- per span name: count, total, self time (the span's time less that of
  the spans nested in it) and the longest one;
- ``idle_in_program_s``: device-idle time (device 0) during which the
  innermost open host span is a program span other than
  ``repro.serve.callback`` (the caller's own code);
- device time per ``XLA Modules`` name (engines are named
  ``engine_<kind>_<policy>_<backend>`` by ``core/dispatcher.py``);
- the busy time and top device operations of ``trace_reduce.reduce_events``,
  and the longest idle gaps of device 0, each labelled by the span whose
  own code (its self time) held most of it;
- the per-batch readings the program spans give (milliseconds a batch).

    python3 chip_bench/program_spans.py <trace dir or .xplane.pb>

prints that as one JSON object.
"""
from __future__ import annotations

import dataclasses
import json
import math
import re
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chip_bench import trace_reduce as tr  # noqa: E402

PROGRAM = "repro."
CALLBACK = "repro.serve.callback"
MODULES_LINE = "XLA Modules"
_HASH = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class ProgramEvents:
    """Intervals in ns on the trace's clock."""

    device_ops: list  # (device, name, start_ns, end_ns), as trace_reduce
    modules: list  # (device, module name, start_ns, end_ns)
    host_lines: list  # per host thread: [(name, start_ns, end_ns)]

    def events(self) -> tr.TraceEvents:
        """Everything as ``trace_reduce.TraceEvents``: its busy time and
        operations are unchanged, its gap labels see program spans too."""
        return tr.TraceEvents(
            self.device_ops, [s for line in self.host_lines for s in line])


def load_events(path: str) -> ProgramEvents:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, lines = [], [], []
    for plane in data.planes:
        m = tr._DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                dev = int(m.group(1))
                out = {tr.OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if out is not None:
                    out.extend((dev, e.name, e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events)
                continue
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in line.events
                     if e.name.startswith((PROGRAM, "bench."))]
            if spans:
                lines.append(spans)
    return ProgramEvents(ops, modules, lines)


def innermost(spans) -> list[tuple[str, float, float]]:
    """One thread's nested spans cut into (name, start, end) pieces, each
    moment under some span given to the innermost one open; the pieces of a
    span are its self time."""
    pieces, stack = [], []  # stack: [name, end, start of its next piece]

    def close(t):
        while stack and stack[-1][1] <= t:
            name, end, cur = stack.pop()
            if end > cur:
                pieces.append((name, cur, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack:
            parent = stack[-1]
            e = min(e, parent[1])
            if s > parent[2]:
                pieces.append((parent[0], parent[2], s))
            parent[2] = max(parent[2], s)
        stack.append([name, e, s])
    close(math.inf)
    return pieces


def _clip(spans, lo: float, hi: float) -> list[tuple[str, float, float]]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in spans
            if e > lo and s < hi]


def _overlap(a, b) -> float:
    """Total length of the intersection of two sorted, disjoint interval
    lists."""
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


def span_table(host_lines, lo: float, hi: float) -> dict:
    """``{name: {"count", "total_s", "self_s", "max_s"}}`` of the spans
    that overlap [lo, hi], their times clipped to it."""
    table: dict = {}
    for line in host_lines:
        for name, s, e in _clip(line, lo, hi):
            row = table.setdefault(name, {"count": 0, "total_s": 0.0,
                                          "self_s": 0.0, "max_s": 0.0})
            row["count"] += 1
            row["total_s"] += (e - s) / 1e9
            row["max_s"] = max(row["max_s"], (e - s) / 1e9)
        for name, s, e in _clip(innermost(line), lo, hi):
            table[name]["self_s"] += (e - s) / 1e9
    return table


def self_pieces(host_lines, lo: float, hi: float) -> list:
    """The innermost pieces of every host thread, clipped to [lo, hi]."""
    return [p for line in host_lines for p in _clip(innermost(line), lo, hi)]


def device_gaps(ev: ProgramEvents, lo: float, hi: float) -> list:
    """Device 0's idle intervals in [lo, hi]."""
    busy = tr.union(tr.clip([(s, e) for d, _, s, e in ev.device_ops
                             if d == 0], lo, hi))
    return tr.gaps(busy, lo, hi)


def idle_in_program_ns(gaps, pieces) -> float:
    """Idle time under a program span: the innermost open span is
    ``repro.*`` and not the callback."""
    program = tr.union([(s, e) for name, s, e in pieces
                        if name.startswith(PROGRAM) and name != CALLBACK])
    return _overlap(gaps, program)


def label_gap(gap, pieces) -> str:
    """The span whose own code ran for most of ``gap``, or ``host.other``.
    (``trace_reduce.label_gap`` gives the span that overlaps the gap most,
    which is the enclosing one wherever the gap spans several children.)"""
    held: dict = {}
    for name, s, e in pieces:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > 0:
            held[name] = held.get(name, 0.0) + overlap
    return max(held, key=held.get) if held else "host.other"


def module_times(modules, lo: float, hi: float) -> dict:
    """Device seconds per ``XLA Modules`` name (hash suffix dropped) in
    [lo, hi], summed over devices."""
    out: dict = {}
    for _, name, s, e in modules:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            key = _HASH.sub("", name)
            out[key] = out.get(key, 0.0) + (e - s) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def per_batch_ms(spans: dict, batches: int) -> dict:
    """Milliseconds a batch from the span table: admission (self of the
    plan), dispatch host work (self of begin and settle), refits, host
    waits on the device, and finalize less the caller's callbacks."""
    if not batches:
        return {}

    def t(name, key):
        return spans.get(name, {}).get(key, 0.0)

    return {k: v * 1e3 / batches for k, v in {
        "admission": t("repro.admission.plan", "self_s"),
        "dispatch_host": (t("repro.dispatch.begin", "self_s")
                          + t("repro.dispatch.settle", "self_s")),
        "refit": t("repro.dispatch.refit", "total_s"),
        "device_wait": t("repro.dispatch.wait_device", "total_s"),
        "finalize": (t("repro.serve.finalize", "total_s")
                     - t(CALLBACK, "total_s")),
    }.items()}


def report(path: str, n_devices: int = 1, top: int = 10) -> dict | None:
    """The whole reading of one trace (module docstring); None when it
    holds no window span or no device operation in it."""
    ev = load_events(path)
    flat = ev.events()
    base = tr.reduce_events(flat, n_devices, top)
    if base is None:
        return None
    lo, hi = next((s, e) for name, s, e in flat.host_spans
                  if name == tr.WINDOW_SPAN)
    spans = span_table(ev.host_lines, lo, hi)
    pieces = self_pieces(ev.host_lines, lo, hi)
    gaps = device_gaps(ev, lo, hi)
    window_self = spans.get(tr.WINDOW_SPAN, {}).get("self_s", 0.0)
    batches = sum(1 for line in ev.host_lines for name, s, _ in line
                  if name == "repro.serve.dispatch" and lo <= s < hi)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return dict(
        base,
        idle_gaps=[[label_gap(g, pieces), (g[1] - g[0]) / 1e9]
                   for g in longest],
        idle_in_program_s=idle_in_program_ns(gaps, pieces) / 1e9,
        # window time under some span nested in the window span
        covered_s=base["window_s"] - window_self,
        batches=batches,
        per_batch_ms=per_batch_ms(spans, batches),
        spans=dict(sorted(spans.items())),
        modules=module_times(ev.modules, lo, hi),
    )


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = args[0]
    if Path(path).is_dir():
        path = tr.find_xplane(path)
    r = report(path) if path else None
    if r is None:
        print(f"no window span or device operation in {args[0]}",
              file=sys.stderr)
        return 1
    print(json.dumps(r, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
