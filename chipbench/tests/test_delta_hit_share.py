"""The Δ key memo's reader (``metrics/delta_hit_share.sat.py``)."""

from __future__ import annotations

import pathlib

import pytest

import run as harness
import spans
from helpers import tiny_run

trace = harness.tracemod
MS = 1_000_000   # nanoseconds
FIXTURE = pathlib.Path(__file__).parent / "data" / "fixture.xplane.pb"
MAIN = ("host", 0)


def _span(start, end, name, **args):
    return spans.Span(start * MS, end * MS, name, MAIN, args)


def _window():
    """Window 0..100 ms with device work; a launch and uncounted store spans."""
    ops = {0: [(10 * MS, 40 * MS, "fusion.1")]}
    t = trace.Trace(ops, {}, [(0, 100 * MS, trace.WINDOW_SPAN)])
    found = [_span(0, 50, "cg.launch", useful=30, slots=120, pad=20),
             _span(2, 20, "cg.store.blocks"),
             _span(5, 8, "cg.store.keys")]
    return t, found


def _read(run):
    path = harness.HERE / "metrics" / "delta_hit_share.sat.py"
    return harness.load_file_module(path).read(run)


def _run_with(found, t=None, **counters):
    run = harness.Run(cell={}, config={}, traffic={}, seed=1, seconds=1.0,
                      trace=True)
    run.trace_data = t
    run.counters.update(counters)
    run._cg_spans = found
    return run


def test_memo_hits_read_as_a_share_of_the_counted_lanes():
    t, found = _window()
    counted = sorted(found + [
        _span(60, 62, "cg.store.blocks", delta_hit=3, delta_miss=1),
        _span(80, 81, "cg.store.blocks", delta_hit=5, delta_miss=0)],
        key=lambda s: (s.start, -s.end))
    assert _read(_run_with(counted, t)) == pytest.approx(100.0 * 8 / 9)
    # A program without the memo: block spans, but none carries the counters.
    assert _read(_run_with(found, t)) is None
    assert _read(_run_with([], t)) is None


def test_a_program_that_predates_the_memo_reads_nothing(monkeypatch):
    # A parent commit's traced run: no span module and no cg. span.
    monkeypatch.setattr(harness, "TRACE_DIR", FIXTURE.parent)
    monkeypatch.setattr(spans, "PROGRAM", "repro.runtime.no_such_module")
    run = _run_with(None, trace.load(FIXTURE), answered=3, queries=1)
    del run._cg_spans
    assert _read(run) is None


def test_cpu_rehearsal_reads_the_memo_share():
    metrics = tiny_run("svc.saturated", trace=True)["metrics"]
    assert 0 <= metrics["delta_hit_share.sat"]["value"] <= 100
