"""The program's own spans and their counters, read from the run's trace.

The program opens host spans named ``cg.<layer>.<stage>``
(``repro.runtime.spans``): ``cg.store.keys`` (set algebra) and
``cg.store.blocks`` (block building) in the store, ``cg.anchor.rebuild``
and ``cg.anchor.hop`` around anchor fixpoints, ``cg.launch`` around each
batched launch. The fixpoint spans carry the counters ``useful``,
``slots`` and ``pad``. They are parsed once per run from the
``.xplane.pb`` the harness loaded, clipped to the window span, and reduced
with the interval helpers of ``trace.py``.

A window whose device ran while no ``cg.launch`` span lies there stops
the run: spans renamed or removed would otherwise read zero unseen. The one
exception is a program that predates the spans, which the traced runs of a
parent commit meet under this harness: no ``cg.`` span in the window and
no ``repro.runtime.spans`` module. Every reader then reads nothing, and the
result line leaves its metric out. A kind of span that did not occur in the
window reads 0.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import sys

#: The program module that opens the spans.
PROGRAM = "repro.runtime.spans"
PREFIX = "cg."
LAUNCH = "cg.launch"
#: Spans around fixpoint work; each carries ``useful``, ``slots``, ``pad``.
FIXPOINT = (LAUNCH, "cg.anchor.")
STORE = "cg.store."
ANCHOR = "cg.anchor."


@dataclasses.dataclass(frozen=True)
class Span:
    """One program span, clipped to the window (nanoseconds)."""

    start: float
    end: float
    name: str
    thread: tuple
    args: dict


def parse(path, window) -> "list[Span]":
    """The ``cg.`` host spans of one trace file overlapping ``window``."""
    from jax.profiler import ProfileData

    t0, t1 = window
    found = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                end = e.start_ns + e.duration_ns
                if e.name.startswith(PREFIX) and end > t0 and e.start_ns < t1:
                    found.append(Span(max(e.start_ns, t0), min(end, t1),
                                      e.name, (plane.name, k), dict(e.stats)))
    return sorted(found, key=lambda s: (s.start, -s.end))


def of_run(run) -> "list[Span] | None":
    """The run's program spans, parsed once; None where nothing can be read."""
    if "_cg_spans" in vars(run):
        return run._cg_spans
    found = None
    trace = run.trace_data
    if trace is not None and trace.window is not None:
        harness = sys.modules[type(run).__module__]
        found = parse(run.trace_lib.find_xplane(harness.TRACE_DIR),
                      trace.window)
        if found or importlib.util.find_spec(PROGRAM) is not None:
            check(run.trace_lib, trace, found)
        else:
            found = None   # the program predates its spans
    run._cg_spans = found
    return found


def check(trace_lib, trace, found) -> None:
    """Stop the run when the device ran in the window but no launch span did."""
    if trace_lib.busy_s(trace) and not any(s.name == LAUNCH for s in found):
        raise RuntimeError(f"the device ran in the window but the trace holds "
                           f"no {LAUNCH} span there: the program's spans were "
                           "renamed or removed")


def total(found, prefixes, key: str) -> float:
    """The counter ``key`` summed over spans whose name starts with a prefix."""
    return sum(s.args.get(key, 0) for s in found if s.name.startswith(prefixes))


def slot_share(found, key: str) -> "float | None":
    """``key`` (``useful`` or ``pad``) over ``slots`` of the fixpoint spans, in %.

    None where nothing can be read or no fixpoint ran in the window.
    """
    if found is None:
        return None
    slots = total(found, FIXPOINT, "slots")
    return 100.0 * total(found, FIXPOINT, key) / slots if slots else None


def self_seconds(found, prefix: str) -> float:
    """Seconds inside spans named ``prefix…``, spans nested in them subtracted."""
    own = {}
    for thread in {s.thread for s in found}:
        stack: list = []
        for s in (x for x in found if x.thread == thread):  # (start, -end)
            while stack and stack[-1].end <= s.start:
                stack.pop()
            own[id(s)] = s.end - s.start
            if stack:
                own[id(stack[-1])] -= s.end - s.start
            stack.append(s)
    return sum(own[id(s)] for s in found if s.name.startswith(prefix)) / 1e9


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        out += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def device_seconds(trace_lib, trace, found, prefix: str) -> "float | None":
    """Device-busy seconds inside spans named ``prefix…`` (mean over devices).

    The union of the device-op intervals in the window, intersected with
    the union of those spans; None without device events.
    """
    win = trace.window
    if win is None or not trace.ops:
        return None
    inside = trace_lib._union([(s.start, s.end, s.name) for s in found
                               if s.name.startswith(prefix)])
    per_device = [_overlap(trace_lib._union(trace_lib._clip(iv, *win)), inside)
                  for iv in trace.ops.values()]
    return sum(per_device) / len(per_device) / 1e9


def idle_seconds(trace_lib, trace, found, prefix: str) -> "float | None":
    """Device 0's idle seconds whose innermost open program span is ``prefix…``.

    As ``trace.idle_gaps``: a gap belongs to the ``cg.`` span that started
    last among those open at its midpoint. None without device events.
    """
    win = trace.window
    if win is None or not trace.ops:
        return None
    busy = trace_lib._union(trace_lib._clip(trace.ops[min(trace.ops)], *win))
    gaps, t = [], win[0]
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < win[1]:
        gaps.append((t, win[1]))
    out = 0.0
    active: list = []
    nxt = 0
    for s, e in gaps:
        mid = (s + e) / 2
        while nxt < len(found) and found[nxt].start <= mid:
            active.append(found[nxt])
            nxt += 1
        active = [x for x in active if x.end > mid]
        inner = max(active, key=lambda x: (x.start, -x.end), default=None)
        if inner is not None and inner.name.startswith(prefix):
            out += e - s
    return out / 1e9
