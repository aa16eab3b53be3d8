"""The program-span reduction (``spans.py``) and the readers built on it."""

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


def _span(start, end, name, thread=MAIN, **args):
    return spans.Span(start * MS, end * MS, name, thread, args)


def _made():
    """Window 0..100 ms, device busy 10-40 and 70-80 ms; program spans."""
    ops = {0: [(10 * MS, 30 * MS, "fusion.1"), (25 * MS, 40 * MS, "fusion.2"),
               (70 * MS, 80 * MS, "fusion.1")]}
    t = trace.Trace(ops, {}, [(0, 100 * MS, trace.WINDOW_SPAN)])
    found = sorted([
        _span(0, 50, "cg.launch", useful=30, slots=120, pad=20),
        _span(2, 20, "cg.store.blocks"),
        _span(5, 8, "cg.store.keys"),
        _span(50, 60, "cg.store.keys"),
        _span(20, 75, "cg.anchor.rebuild", thread=("host", 1), useful=10,
              slots=80, pad=0),
    ], key=lambda s: (s.start, -s.end))
    return t, found


def _reader(name):
    return harness.load_file_module(harness.HERE / "metrics" / f"{name}.py")


def _run_with(found, t=None, **counters):
    run = harness.Run(cell={}, config={}, traffic={}, seed=1, seconds=1.0,
                      trace=True)
    run.trace_data = t
    run.counters.update(counters)
    run._cg_spans = found
    return run


def test_self_seconds_subtract_nested_spans_on_their_thread():
    _, found = _made()
    assert spans.self_seconds(found, "cg.store.keys") == pytest.approx(0.013)
    assert spans.self_seconds(found, "cg.store.blocks") == pytest.approx(0.015)
    # 50 ms minus blocks (18) nested; the rebuild on thread 1 is not nested.
    assert spans.self_seconds(found, "cg.launch") == pytest.approx(0.032)
    assert spans.self_seconds(found, "cg.anchor.") == pytest.approx(0.055)


def test_idle_goes_to_the_innermost_open_program_span():
    t, found = _made()
    # Gaps 0-10 (midpoint 5: keys opened at 5), 40-70 (55: keys 50-60,
    # inside the rebuild, which opened earlier), 80-100 (90: none).
    assert spans.idle_seconds(trace, t, found, spans.STORE) == \
        pytest.approx(0.040)
    assert spans.idle_seconds(trace, t, found, spans.ANCHOR) == 0
    assert spans.idle_seconds(trace, trace.Trace({}, {}, t.spans), found,
                              spans.STORE) is None


def test_device_seconds_are_clipped_to_the_spans():
    t, found = _made()
    # Busy 10-40 and 70-80 inside the rebuild's 20-75: 20 + 5 ms.
    assert spans.device_seconds(trace, t, found, spans.ANCHOR) == \
        pytest.approx(0.025)
    assert spans.device_seconds(trace, t, found, spans.LAUNCH) == \
        pytest.approx(0.030)
    assert spans.device_seconds(trace, t, [], spans.ANCHOR) == 0


def test_counters_read_as_shares():
    t, found = _made()
    run = _run_with(found, t, queries=2)
    for cell in ("hist", "sat"):
        assert _reader(f"useful_share.{cell}").read(run) == \
            pytest.approx(20.0)
        assert _reader(f"pad_share.{cell}").read(run) == pytest.approx(10.0)
    store_only = [s for s in found if s.name.startswith(spans.STORE)]
    assert _reader("useful_share.sat").read(_run_with(store_only, t)) is None
    assert _reader("fixpoint_device_s_anchor.hist").read(run) == \
        pytest.approx(0.0125)


def test_a_window_without_set_algebra_reads_zero():
    t, found = _made()
    no_keys = [s for s in found if s.name != "cg.store.keys"]
    run = _run_with(no_keys, t, answered=4)
    assert _reader("keys_s.sat").read(run) == 0
    assert _reader("blocks_s.sat").read(run) == pytest.approx(0.018 / 4)
    assert _reader("idle_share_prep.sat").read(run) == pytest.approx(10.0)


def test_a_busy_window_without_launch_spans_stops_the_run(monkeypatch):
    # The recorded TPU trace ran device work but holds no program span.
    monkeypatch.setattr(harness, "TRACE_DIR", FIXTURE.parent)
    run = _run_with(None, trace.load(FIXTURE), answered=3)
    del run._cg_spans
    with pytest.raises(RuntimeError, match="no cg.launch span"):
        _reader("keys_s.sat").read(run)
    t, found = _made()
    with pytest.raises(RuntimeError, match="renamed or removed"):
        spans.check(trace, t, [s for s in found if s.name != "cg.launch"])
    spans.check(trace, trace.Trace({}, {}, t.spans), [])   # no device: no fault


READERS = ("keys_s.sat", "blocks_s.sat", "idle_share_prep.sat",
           "fixpoint_device_s_anchor.sat", "useful_share.sat",
           "pad_share.sat", "useful_share.hist", "pad_share.hist",
           "fixpoint_device_s_anchor.hist")


def test_a_program_that_predates_its_spans_reads_nothing(monkeypatch):
    # A parent commit's traced run: no span module and no cg. span.
    monkeypatch.setattr(harness, "TRACE_DIR", FIXTURE.parent)
    monkeypatch.setattr(spans, "PROGRAM", "repro.runtime.no_such_module")
    run = _run_with(None, trace.load(FIXTURE), answered=3, queries=1)
    del run._cg_spans
    for name in READERS:
        assert _reader(name).read(run) is None


def test_spans_are_read_and_checked_whatever_the_module(monkeypatch):
    # A moved span module does not silence spans that are in the trace.
    monkeypatch.setattr(harness, "TRACE_DIR", FIXTURE.parent)
    monkeypatch.setattr(spans, "PROGRAM", "repro.runtime.no_such_module")
    t, found = _made()
    monkeypatch.setattr(spans, "parse", lambda path, window: found)
    run = _run_with(None, t, answered=4, queries=2)
    del run._cg_spans
    assert _reader("useful_share.sat").read(run) == pytest.approx(20.0)
    store_only = [s for s in found if s.name.startswith(spans.STORE)]
    monkeypatch.setattr(spans, "parse", lambda path, window: store_only)
    run = _run_with(None, t, answered=4)
    del run._cg_spans
    with pytest.raises(RuntimeError, match="no cg.launch span"):
        _reader("keys_s.sat").read(run)


@pytest.mark.parametrize("cell,names", [
    ("hist8.sssp", ("useful_share.hist", "pad_share.hist")),
    ("svc.saturated", ("keys_s.sat", "blocks_s.sat", "useful_share.sat",
                       "pad_share.sat"))])
def test_cpu_rehearsal_reads_the_program_spans(cell, names):
    metrics = tiny_run(cell, trace=True)["metrics"]
    assert set(names) <= set(metrics)
    for name in names:
        value = metrics[name]["value"]
        assert 0 <= value <= 100 if "share" in name else value >= 0
    if cell == "hist8.sssp":
        assert metrics["useful_share.hist"]["value"] > 0
        assert metrics["pad_share.hist"]["value"] < 100
    else:
        assert metrics["blocks_s.sat"]["value"] > 0
        assert metrics["useful_share.sat"]["value"] > 0
