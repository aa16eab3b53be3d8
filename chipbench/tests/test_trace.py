"""The trace reduction (``trace.py``) on hand-made intervals and on a recorded TPU trace."""

from __future__ import annotations

import pathlib

import pytest

import run as harness

trace = harness.tracemod
MS = 1_000_000   # nanoseconds
#: Recorded on one TPU v5e by ``data/record_fixture.py``.
FIXTURE = pathlib.Path(__file__).parent / "data" / "fixture.xplane.pb"


def test_recorded_tpu_trace():
    t = trace.load(FIXTURE)
    assert trace.find_xplane(FIXTURE.parent) == FIXTURE
    assert list(t.ops) == [0] and list(t.modules) == [0]
    # cb.window: three rounds of ~0.125 s (20 ms store + 100 ms wait + jits).
    assert 0.36 < trace.window_s(t) < 0.45
    busy = trace.busy_s(t)
    assert 0 < busy < 0.01 and trace.idle_share(t) == pytest.approx(
        100 * (1 - busy / trace.window_s(t)))
    gaps = trace.idle_gaps(t)
    assert [name for name, _ in gaps[:2]] == ["cb.wait", "cb.store"]
    assert dict(gaps)["cb.wait"] == pytest.approx(0.3, abs=0.02)
    assert dict(gaps)["cb.store"] == pytest.approx(0.06, abs=0.01)
    assert sum(s for _, s in gaps) == pytest.approx(trace.window_s(t) - busy)
    ops = trace.top_ops(t)
    assert "fusion" in ops[0][0] and ops[0][1] > ops[1][1]
    assert trace.module_seconds(t, [r"jit__lambda"]) == pytest.approx(
        busy, rel=0.1)


def _made():
    """Window 0..100 ms; device busy 10-30, 25-40 and 70-80 ms on device 0."""
    ops = {0: [(10 * MS, 30 * MS, "fusion.1"), (25 * MS, 40 * MS, "fusion.2"),
               (70 * MS, 80 * MS, "fusion.1"), (150 * MS, 160 * MS, "late")]}
    modules = {0: [(10 * MS, 40 * MS, "jit__fixpoint_jit(12)"),
                   (70 * MS, 80 * MS, "jit_other(3)")]}
    spans = [(0, 100 * MS, trace.WINDOW_SPAN),
             (0, 45 * MS, "cb.turn"), (40 * MS, 45 * MS, "cb.store"),
             (45 * MS, 70 * MS, "cb.wait")]
    return trace.Trace(ops, modules, sorted(spans))


def test_busy_and_idle_share_use_the_union_inside_the_window():
    t = _made()
    assert t.window == (0, 100 * MS)
    assert trace.window_s(t) == pytest.approx(0.1)
    assert trace.busy_s(t) == pytest.approx(0.040)   # 10-40 and 70-80
    assert trace.idle_share(t) == pytest.approx(60.0)


def test_busy_averages_over_devices():
    t = _made()
    t.ops[1] = [(0, 100 * MS, "all")]
    assert trace.busy_s(t) == pytest.approx((0.040 + 0.1) / 2)


def test_module_seconds_match_patterns():
    t = _made()
    assert trace.module_seconds(t, [r"_fixpoint_jit"]) == pytest.approx(0.030)
    assert trace.module_seconds(t, [r"_fixpoint_jit", r"jit_other"]) == \
        pytest.approx(0.040)
    assert trace.module_seconds(t, [r"no_such_module"]) is None


def test_top_ops_sum_per_name_inside_the_window():
    ops = dict(trace.top_ops(_made()))
    assert ops["fusion.1"] == pytest.approx(0.030)
    assert ops["fusion.2"] == pytest.approx(0.015)
    assert "late" not in ops


def test_idle_gaps_are_named_by_the_innermost_open_span():
    gaps = dict(trace.idle_gaps(_made()))
    # 0-10 in cb.turn; 40-70: midpoint 55 in cb.wait; 80-100: no span open.
    assert gaps == pytest.approx({"cb.turn": 0.010, "cb.wait": 0.030,
                                  "none": 0.020})


def test_no_window_or_no_device_reads_nothing():
    t = _made()
    empty = trace.Trace({}, {}, t.spans)
    assert trace.busy_s(empty) is None and trace.idle_share(empty) is None
    no_window = trace.Trace(t.ops, t.modules,
                            [s for s in t.spans if s[2] != trace.WINDOW_SPAN])
    assert trace.window_s(no_window) is None
    assert trace.top_ops(no_window) == [] and trace.idle_gaps(no_window) == []
