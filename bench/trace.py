"""Reduction of a profiler trace to what the per-layer readers need.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Its device planes (``/device:TPU:<i>``) carry one line of XLA operations
(``XLA Ops``) and one of whole programs (``XLA Modules``); the host plane
carries the benchmark's spans as ``bench:<name>`` annotations on the same
clock, and the program's own spans as ``td:<name>`` annotations. From
these:

* busy intervals: the union of the operation intervals of each device;
* the traced window: the ``bench:window`` span;
* device time inside a set of spans, idle gaps labelled by the innermost
  span they fall in, and the operations and programs that took most time;
* the program's spans, and device 0's programs one by one, as intervals.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Tuple

from bench.record import SPAN_PREFIX

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
#: the program's span annotations (``repro.serving.trace.SPAN_PREFIX``)
PROGRAM_SPAN_PREFIX = "td:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged: List[Interval], s: float, e: float) -> float:
    """Length of ``[s, e)`` covered by the disjoint ``merged`` intervals."""
    return sum(max(0.0, min(e, b) - max(s, a)) for a, b in merged
               if a < e and b > s)


@dataclass
class Reduced:
    """A trace reduced to intervals in nanoseconds on the host clock."""

    window: Interval
    busy: List[List[Interval]]            # per device, merged
    spans: List[Tuple[str, float, float]]  # host spans, prefix stripped
    ops: Dict[str, float] = field(default_factory=dict)      # name -> ns
    modules: Dict[str, float] = field(default_factory=dict)  # name -> ns
    #: the program's host spans, prefix stripped
    program_spans: List[Tuple[str, float, float]] = field(default_factory=list)
    #: device 0's programs, each run as (name, start, end)
    module_events: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over devices."""
        if not self.busy:
            return 0.0
        w0, w1 = self.window
        return sum(overlap(b, w0, w1) for b in self.busy) / len(self.busy) \
            * 1e-9

    def busy_in(self, name: str) -> float:
        """Busy seconds inside spans called ``name``, averaged over
        devices."""
        if not self.busy:
            return 0.0
        iv = union([(s, e) for n, s, e in self.spans if n == name])
        tot = sum(overlap(b, s, e) for b in self.busy for s, e in iv)
        return tot / len(self.busy) * 1e-9

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The ``top`` longest idle gaps of device 0 in the window, each
        named by the innermost span its midpoint falls in."""
        if not self.busy:
            return []
        w0, w1 = self.window
        edges = [w0] + [x for iv in self.busy[0] for x in iv] + [w1]
        gaps = []
        for i in range(0, len(edges), 2):
            s, e = max(edges[i], w0), min(edges[i + 1], w1)
            if e > s:
                gaps.append((s, e))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self.label((s + e) / 2), (e - s) * 1e-9)
                for s, e in gaps[:top]]

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds of device 0 summed by the innermost span."""
        out: Dict[str, float] = defaultdict(float)
        for name, secs in self.idle_gaps(top=1 << 30):
            out[name] += secs
        return dict(out)

    def label(self, t: float) -> str:
        inner = [(e - s, n) for n, s, e in self.spans
                 if s <= t < e and n != "window"]
        return min(inner)[1] if inner else "window"


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce_xspace(data) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``."""
    busy, spans, program_spans, module_events = [], [], [], []
    ops: Dict[str, float] = defaultdict(float)
    modules: Dict[str, float] = defaultdict(float)
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        if dev:
            ivs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        ivs.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                        ops[ev.name] += ev.duration_ns
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        name = _module_name(ev.name)
                        modules[name] += ev.duration_ns
                        if dev.group(2) == "0":
                            module_events.append(
                                (name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
            busy.append(union(ivs))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],) + iv)
                    elif ev.name.startswith(PROGRAM_SPAN_PREFIX):
                        program_spans.append(
                            (ev.name[len(PROGRAM_SPAN_PREFIX):],) + iv)
    win = [(s, e) for n, s, e in spans if n == "window"]
    if win:
        window = (min(s for s, _ in win), max(e for _, e in win))
    elif spans:
        window = (min(s for _, s, _ in spans), max(e for _, _, e in spans))
    else:
        window = (0.0, 0.0)
    return Reduced(window, busy, spans, dict(ops), dict(modules),
                   sorted(program_spans, key=lambda x: x[1]),
                   sorted(module_events, key=lambda x: x[1]))


def load(trace_dir: Path) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_xspace(ProfileData.from_file(str(find_xplane(trace_dir))))


def top(table: Dict[str, float], n: int = 10) -> List[list]:
    """The ``n`` largest entries of a name -> ns table, in seconds."""
    return [[k, v * 1e-9] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]
