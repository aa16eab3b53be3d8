"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

What it gives, all over the traced window (the host span ``WINDOW_SPAN``):
the seconds in which some operation ran on each device (the union of the
device-op intervals), device seconds per XLA module name pattern, the
device operations that took most time, and the idle gaps, each named by
the benchmark's innermost host span open at the gap's midpoint. Device
and host planes share one clock in the trace.
"""

from __future__ import annotations

import dataclasses
import pathlib
import re

#: Host spans the benchmark opens start with this prefix.
SPAN_PREFIX = "cb."
#: The host span that marks the measured window.
WINDOW_SPAN = "cb.window"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"


@dataclasses.dataclass
class Trace:
    """Intervals in nanoseconds, as ``(start, end, name)`` tuples."""

    ops: "dict[int, list[tuple[float, float, str]]]"      # device id -> ops
    modules: "dict[int, list[tuple[float, float, str]]]"  # device id -> modules
    spans: "list[tuple[float, float, str]]"               # benchmark host spans

    @property
    def window(self) -> "tuple[float, float] | None":
        marks = [(s, e) for s, e, n in self.spans if n == WINDOW_SPAN]
        return marks[0] if marks else None


def find_xplane(log_dir: "str | pathlib.Path") -> "pathlib.Path | None":
    """The newest ``.xplane.pb`` under a profiler log directory."""
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def load(path: "str | pathlib.Path") -> Trace:
    """Read device ops, XLA modules and benchmark spans from one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: dict = {}
    modules: dict = {}
    spans: list = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(2))
            for line in plane.lines:
                if line.name in (_OPS_LINE, _MODULES_LINE):
                    out = ops if line.name == _OPS_LINE else modules
                    out.setdefault(dev, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    for dev in modules:   # a trace with no op line falls back on its modules
        ops.setdefault(dev, modules[dev])
    return Trace(ops, modules, sorted(spans))


def _clip(intervals, t0: float, t1: float):
    return [(max(s, t0), min(e, t1), n) for s, e, n in intervals
            if e > t0 and s < t1]


def _union(intervals) -> "list[tuple[float, float]]":
    merged: list = []
    for s, e, _ in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_s(trace: Trace) -> "float | None":
    """Seconds in which an operation ran, averaged over the devices traced."""
    win = trace.window
    if win is None or not trace.ops:
        return None
    per_device = [sum(e - s for s, e in _union(_clip(iv, *win)))
                  for iv in trace.ops.values()]
    return sum(per_device) / len(per_device) / 1e9


def window_s(trace: Trace) -> "float | None":
    win = trace.window
    return None if win is None else (win[1] - win[0]) / 1e9


def idle_share(trace: Trace) -> "float | None":
    """1 - busy / window, as a percentage; None without device events."""
    busy, win = busy_s(trace), window_s(trace)
    if busy is None or not win or busy == 0:
        return None
    return 100.0 * (1.0 - busy / win)


def module_seconds(trace: Trace, patterns) -> "float | None":
    """Device seconds in modules whose name matches any pattern (per device mean)."""
    win = trace.window
    if win is None or not trace.modules:
        return None
    rx = re.compile("|".join(patterns))
    per_device = []
    for iv in trace.modules.values():
        hits = [x for x in _clip(iv, *win) if rx.search(x[2])]
        per_device.append(sum(e - s for s, e in _union(hits)))
    total = sum(per_device) / len(per_device)
    return total / 1e9 if total > 0 else None


def top_ops(trace: Trace, k: int = 10) -> "list[list]":
    """The ``k`` device operations with the most seconds in the window."""
    win = trace.window
    if win is None:
        return []
    seconds: dict = {}
    for iv in trace.ops.values():
        for s, e, name in _clip(iv, *win):
            seconds[name] = seconds.get(name, 0.0) + (e - s) / 1e9
    return [[n, v] for n, v in sorted(seconds.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> "list[list]":
    """Idle seconds of device 0's timeline, summed per innermost host span.

    A gap is named by the benchmark span that started last among those
    open at its midpoint (``"none"`` when no span was open).
    """
    win = trace.window
    if win is None or not trace.ops:
        return []
    dev = min(trace.ops)
    busy = _union(_clip(trace.ops[dev], *win))
    gaps, t = [], win[0]
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < win[1]:
        gaps.append((t, win[1]))
    inner = [x for x in trace.spans if x[2] != WINDOW_SPAN]   # sorted by start
    seconds: dict = {}
    active: list = []
    nxt = 0
    for s, e in gaps:   # in time order, so one pass over the spans
        mid = (s + e) / 2
        while nxt < len(inner) and inner[nxt][0] <= mid:
            active.append(inner[nxt])
            nxt += 1
        active = [x for x in active if x[1] > mid]
        name = max(active)[2] if active else "none"
        seconds[name] = seconds.get(name, 0.0) + (e - s) / 1e9
    return [[n, v] for n, v in sorted(seconds.items(), key=lambda x: -x[1])[:k]]
