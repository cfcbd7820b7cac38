"""From a profiler trace to device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three things: each chip's op intervals (the ``XLA Ops`` line of a
``/device:TPU:n`` plane), each chip's program intervals (``XLA Modules``,
named after the jitted function), and the host spans the benchmark
records with ``TraceAnnotation`` (names starting ``bench.``).  Everything
else the reduction computes from those lists, so tests run it on a small
recorded trace without a chip.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"   # encloses the traced part of the window


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Interval]]       # chip plane -> op intervals
    modules: Dict[str, List[Interval]]   # chip plane -> program intervals
    spans: List[Interval]                # the benchmark's host spans
    window: Tuple[int, int]              # traced window (first/last span)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is None:
                    continue
                dest.setdefault(plane.name, []).extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    for d in (ops, modules):
        for v in d.values():
            v.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: e[1])
    outer = [s for s in spans if s[0] == WINDOW_SPAN]
    if outer:
        window = (outer[0][1], outer[0][2])
    else:
        window = _extent(spans or [e for v in ops.values() for e in v])
    return Trace(ops, modules, [s for s in spans if s[0] != WINDOW_SPAN],
                 window)


def _extent(events: List[Interval]) -> Tuple[int, int]:
    if not events:
        return (0, 0)
    return (min(e[1] for e in events), max(e[2] for e in events))


def _clip(events: List[Interval], window) -> List[Interval]:
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events: List[Interval]) -> List[Tuple[int, int]]:
    """Merged busy intervals of possibly overlapping events."""
    merged: List[List[int]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_s(trace: Trace) -> float:
    """Seconds with an op running on the device inside the window,
    averaged over the chips traced."""
    if not trace.ops:
        return 0.0
    per_chip = [sum(e - s for s, e in union(_clip(v, trace.window)))
                for v in trace.ops.values()]
    return sum(per_chip) * 1e-9 / len(per_chip)


def idle_share(trace: Trace) -> Optional[float]:
    if trace.window_s <= 0 or not trace.ops:
        return None
    return 1.0 - busy_s(trace) / trace.window_s


def module_time(trace: Trace, pattern: str) -> Tuple[float, int]:
    """(device seconds, executions) of the programs whose name matches
    ``pattern`` (a regular expression), over all chips, in the window."""
    rx = re.compile(pattern)
    total, count = 0, 0
    for v in trace.modules.values():
        for n, s, e in _clip(v, trace.window):
            if rx.search(n):
                total += e - s
                count += 1
    return total * 1e-9, count


def op_time(trace: Trace, pattern: str) -> Tuple[float, int]:
    """(device seconds, events) of the ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    total, count = 0, 0
    for v in trace.ops.values():
        for n, s, e in _clip(v, trace.window):
            if rx.search(n):
                total += e - s
                count += 1
    return total * 1e-9, count


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """The ``n`` op names that took the most device time, summed over
    their events (and over chips)."""
    acc: Dict[str, int] = {}
    for v in trace.ops.values():
        for name, s, e in _clip(v, trace.window):
            name = name.split(" = ")[0]     # "%fusion.12 = <HLO text>"
            acc[name] = acc.get(name, 0) + e - s
    return [[k, t * 1e-9] for k, t in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> List[list]:
    """The ``n`` longest stretches with no op on the first chip, each
    named by the host span that covers most of it (``idle`` where none
    does)."""
    if not trace.ops:
        return []
    chip = sorted(trace.ops)[0]
    lo, hi = trace.window
    busy = union(_clip(trace.ops[chip], trace.window))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    out = []
    for s, e in gaps[:n]:
        best, cover = "idle", 0
        for name, a, b in trace.spans:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = name, c
        out.append([best, (e - s) * 1e-9])
    return out
