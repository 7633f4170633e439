"""Reduce a JAX profiler trace to device busy time, idle gaps named by the
host span they fall in, and per-operation device time.

The trace is read with ``jax.profiler.ProfileData``.  Device operations are
the events of the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane; host
spans are the benchmark's ``TraceAnnotation`` events on the host plane.
Both carry start times on the profiler's common clock.  The interval
arithmetic below takes plain ``(start_ns, end_ns)`` pairs so that it can be
checked on a small recorded trace without a chip."""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def union(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(i) for i in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(intervals, lo, hi) -> float:
    """Length of the part of [lo, hi] that the disjoint ``intervals`` cover."""
    return float(sum(e - s for s, e in clip(intervals, lo, hi)))


def gaps(busy, lo, hi):
    """Idle intervals of [lo, hi] between the disjoint busy intervals."""
    out, at = [], lo
    for s, e in clip(busy, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def short_name(hlo: str) -> str:
    """``fusion.390`` of ``%fusion.390 = f32[8] fusion(...), ...``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def self_times(events):
    """(name, self time) of (name, start, end) events: each event's length
    less the events nested directly inside it (one that only overlaps
    another is not nested in it)."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and (s >= stack[-1][2] or e > stack[-1][2]):
            out.append(tuple(stack.pop()[::3]))
        if stack:
            stack[-1][3] -= e - s
        stack.append([name, s, e, e - s])
    out.extend(tuple(x[::3]) for x in reversed(stack))
    return out


def innermost(spans, t):
    """Name of the shortest host span that contains time ``t``; "loop" (the
    benchmark's own code between calls) where none does."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "loop"


def attribute_gaps(idle, spans):
    """Idle seconds summed by the innermost host span at each gap's middle,
    longest first."""
    acc = defaultdict(float)
    for s, e in idle:
        acc[innermost(spans, (s + e) / 2)] += (e - s) * 1e-9
    return sorted(acc.items(), key=lambda kv: -kv[1])


@dataclass
class Trace:
    """What one traced window holds, on the profiler's clock (ns)."""
    window: tuple                      # (start, end) of the "window" span
    spans: list                        # (name, start, end) host spans
    ops: dict                          # device index -> [(name, s, e)]
    planes: list = field(default_factory=list)   # names seen, for the log

    def busy(self, dev: int):
        return union((s, e) for _, s, e in self.ops.get(dev, ()))

    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over the chips."""
        lo, hi = self.window
        per = [covered(self.busy(d), lo, hi) for d in self.ops]
        return sum(per) / max(len(per), 1) * 1e-9

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_in(self, name: str, dev: int = 0):
        """(span seconds, device-busy seconds) summed over the spans named
        ``name`` inside the window."""
        busy, lo, hi = self.busy(dev), *self.window
        tot = on = 0.0
        for n, s, e in self.spans:
            if n == name and s >= lo and e <= hi:
                tot += e - s
                on += covered(busy, s, e)
        return tot * 1e-9, on * 1e-9

    def idle_by_span(self, dev: int = 0):
        lo, hi = self.window
        inner = [sp for sp in self.spans if sp[0] != "window"]
        return attribute_gaps(gaps(self.busy(dev), lo, hi), inner)

    def top_ops(self, n: int = 10):
        """The ops with most self time in the window (a loop's time less
        the ops nested in it), by instruction name, over all chips."""
        lo, hi = self.window
        acc = defaultdict(float)
        for evs in self.ops.values():
            for name, t in self_times(e for e in evs
                                      if e[1] >= lo and e[2] <= hi):
                acc[short_name(name)] += t * 1e-9
        return sorted(acc.items(), key=lambda kv: -kv[1])[:n]


def load(trace_dir: str, chips: int, span_names) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops, spans, planes = {}, [], []
    wanted = set(span_names) | {"window"}
    for plane in pd.planes:
        planes.append(plane.name)
        m = _DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            for line in plane.lines:
                planes.append(f"{plane.name}|{line.name}")
                if line.name == OPS_LINE:
                    # on a TPU an op's event name is its HLO instruction
                    ops[int(m.group(1))] = [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if not windows:
        raise RuntimeError("the trace holds no window span")
    if not ops:
        raise RuntimeError(f"the trace holds no '{OPS_LINE}' line on a TPU "
                           f"plane; planes: {planes}")
    return Trace(window=windows[0], spans=spans, ops=ops, planes=planes)
