"""Program spans (``repro.runtime.spans``): cost when off, names, nesting, counters.

Every program span is ``cg.<layer>.<stage>``: ``cg.store.keys`` and
``cg.store.blocks`` around host preparation, ``cg.anchor.rebuild`` and
``cg.anchor.hop`` around anchor fixpoints, ``cg.launch`` around one batched
launch. The fixpoint spans carry ``useful`` (the launch's edge work),
``slots`` (edge slots its sweeps executed) and ``pad`` (sentinel slots among
them). The counts are checked here against the stacked blocks themselves:
their shapes, the sentinel ``dst`` entries read back from the device, the
lane bucket and the sweep counts the run reports.
"""

import importlib
import importlib.util
import pathlib
import sys

import jax
import numpy as np
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import (
    QueryService,
    SnapshotStore,
    optimal_plan,
    plan_levels,
    run_plan_batched,
    run_window_stream_batched,
    stream_campaigns,
)
from repro.graph import make_evolving_sequence
from repro.graph.edgeset import lane_bucket
from repro.graph.semiring import ALL_SEMIRINGS
from repro.runtime import spans

SSSP = ALL_SEMIRINGS["sssp"]
FIXPOINT = ("cg.launch", "cg.anchor.rebuild", "cg.anchor.hop")


def _store(snaps=6):
    return SnapshotStore(make_evolving_sequence(300, 2400, snaps, 150,
                                                seed=11), granule=128)


def _cg_spans(log_dir) -> list:
    """``(start, end, name, thread, args)`` of every ``cg.`` host event."""
    path = max(pathlib.Path(log_dir).rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)
    found = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            found.extend((e.start_ns, e.start_ns + e.duration_ns, e.name,
                          (plane.name, k), dict(e.stats))
                         for e in line.events if e.name.startswith("cg."))
    return sorted(found, key=lambda s: (s[0], -s[1]))


def _traced(tmp_path, fn):
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    return out, _cg_spans(tmp_path)


def _parent(found, s) -> "str | None":
    """Name of the innermost span on ``s``'s thread that encloses ``s``."""
    outer = [p for p in found if p is not s and p[3] == s[3]
             and p[0] <= s[0] and s[1] <= p[1]]
    return min(outer, key=lambda p: p[1] - p[0])[2] if outer else None


def _per_sweep(blocks, bucket, n) -> "tuple[int, int]":
    """(slots, sentinel slots) one sweep of ``bucket`` lanes runs over ``blocks``.

    A shared block (1-D) is swept once per lane; a stacked block holds
    every lane's row already. Sentinels are read back from the device.
    """
    slots = pad = 0
    for b in blocks:
        dst = np.asarray(b.dst)
        copies = bucket if dst.ndim == 1 else 1
        slots += copies * dst.size
        pad += copies * int((dst == n).sum())
    return slots, pad


def _counts(found, names=FIXPOINT):
    return [(s[2], s[4]) for s in found if s[2] in names]


class _Counting(TraceAnnotation):
    made = 0

    def __init__(self, *args, **kwargs):
        _Counting.made += 1
        super().__init__(*args, **kwargs)


def test_off_returns_the_shared_noop_and_builds_no_annotation(monkeypatch,
                                                              tmp_path):
    monkeypatch.setattr(spans, "TraceAnnotation", _Counting)
    _Counting.made = 0
    a, b = spans.span("cg.a"), spans.span("cg.b")
    assert a is b and not a.live
    with a as sp:
        sp.set(useful=1)
    assert _Counting.made == 0
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("cg.test") as sp:
            assert sp.live
            sp.set(useful=3, slots=2**40)
    assert _Counting.made == 1
    assert _counts(_cg_spans(tmp_path), ("cg.test",)) == [
        ("cg.test", {"useful": 3, "slots": 2**40})]


def test_plan_spans_nest_and_count_every_slot(tmp_path):
    store = _store()
    plan = optimal_plan(store)          # window keys are cached from here on
    run, found = _traced(tmp_path, lambda: run_plan_batched(store, plan,
                                                            SSSP, 0))
    names = [s[2] for s in found]
    assert names.count("cg.anchor.rebuild") == 1
    assert names.count("cg.launch") == len(run.hop_stats) == len(
        plan_levels(plan))
    assert "cg.store.keys" in names and "cg.store.blocks" in names
    for s in found:
        parent = _parent(found, s)
        if s[2] in FIXPOINT:
            assert parent is None
        elif s[2] == "cg.store.blocks":
            assert parent in FIXPOINT
        else:       # the Δ set algebra inside a stacked block's building
            assert s[2] == "cg.store.keys" and parent == "cg.store.blocks"

    counted = _counts(found)
    stats = [run.base_stats] + run.hop_stats
    assert [a["useful"] for _, a in counted] == [s.edge_work for s in stats]
    assert sum(a["useful"] for _, a in counted) == \
        run.base_stats.edge_work + sum(h.edge_work for h in run.hop_stats)

    n = store.num_nodes
    apex = store.common_graph_view(*plan.window).blocks
    slots, pad = _per_sweep(apex, 1, n)
    expected = [(run.base_stats.sweeps * slots, run.base_stats.sweeps * pad)]
    prev = [plan]
    for level, st in zip(plan_levels(plan), run.hop_stats):
        bucket = lane_bucket(len(level), 1)
        hops = [(prev[pi].window, c.window) for pi, c in level]
        deltas = (store.delta_stack(hops, num_lanes=bucket),)
        if any(p != plan.window for p, _ in hops):
            deltas = (store.delta_stack([(plan.window, p) for p, _ in hops],
                                        num_lanes=bucket),) + deltas
        seed_slots, seed_pad = _per_sweep(deltas[-1:], bucket, n)
        slots, pad = _per_sweep(apex + deltas, bucket, n)
        expected.append((seed_slots + (st.sweeps - 1) * slots,
                         seed_pad + (st.sweeps - 1) * pad))
        prev = [c for _, c in level]
    assert [(a["slots"], a["pad"]) for _, a in counted] == expected
    assert all(0 < a["useful"] <= a["slots"] - a["pad"] for _, a in counted)


def test_stream_anchor_hops_and_launches_count_every_slot(tmp_path):
    store = _store(snaps=7)
    windows = [(a, a + 1) for a in range(6)]
    run, found = _traced(tmp_path, lambda: run_window_stream_batched(
        store, SSSP, 0, windows=windows, campaign_width=2))
    assert run.anchor_events == ["rebuild", "hop", "hop"]
    counted = _counts(found)
    assert [name for name, _ in counted] == [
        "cg.anchor.rebuild", "cg.launch", "cg.anchor.hop", "cg.launch",
        "cg.anchor.hop", "cg.launch"]
    stats = [s for pair in zip(run.anchor_stats, run.hop_stats) for s in pair]
    assert [a["useful"] for _, a in counted] == [s.edge_work for s in stats]

    n = store.num_nodes
    expected = []
    for k, campaign in enumerate(stream_campaigns(windows, 2)):
        anchor, st = run.anchors[k], run.anchor_stats[k]
        if k == 0:
            view = store.common_graph_view(*anchor).blocks
            slots, pad = _per_sweep(view, 1, n)
            expected.append((st.sweeps * slots, st.sweeps * pad))
        else:   # the hop from the previous anchor: Δ seed, then the fixpoint
            delta = (store.delta_block(run.anchors[k - 1], anchor),)
            view = store.common_graph_view(*run.anchors[k - 1]).blocks + delta
            seed_slots, seed_pad = _per_sweep(delta, 1, n)
            slots, pad = _per_sweep(view, 1, n)
            expected.append((seed_slots + (st.sweeps - 1) * slots,
                             seed_pad + (st.sweeps - 1) * pad))
        bucket = run.lane_layout[k][1]
        stacked = (store.slide_stack(campaign, anchor, num_lanes=bucket),)
        seed_slots, seed_pad = _per_sweep(stacked, bucket, n)
        slots, pad = _per_sweep(view + stacked, bucket, n)
        sweeps = run.hop_stats[k].sweeps - 1
        expected.append((seed_slots + sweeps * slots, seed_pad + sweeps * pad))
    assert [(a["slots"], a["pad"]) for _, a in counted] == expected


def _serve(store):
    """Two clients of one query, one window per campaign: a rebuild, then hops."""
    service = QueryService(store, lane_budget=4)
    a = service.register(SSSP, 0, campaign_width=1, name="a")
    b = service.register(SSSP, 0, campaign_width=1, name="b")
    service.submit(a, [(0, 1), (1, 2), (2, 3)])
    service.submit(b, [(1, 2), (2, 3)])
    service.drain()
    return service, {**{("a",) + w: v for w, v in a.results.items()},
                     **{("b",) + w: v for w, v in b.results.items()}}


def test_service_turn_spans_nest_and_sum_to_its_edge_work(tmp_path):
    (service, _), found = _traced(tmp_path, lambda: _serve(_store()))
    names = [s[2] for s in found]
    assert {"cg.store.keys", "cg.store.blocks", "cg.anchor.rebuild",
            "cg.anchor.hop", "cg.launch"} <= set(names)
    assert names.count("cg.launch") == len(service.launch_log)
    for s in found:
        parent = _parent(found, s)
        if s[2] in FIXPOINT:
            assert parent is None
        else:
            assert s[2].startswith("cg.store.")
            assert parent in FIXPOINT + ("cg.store.blocks", None)
    useful = sum(a["useful"] for _, a in _counts(found))
    assert useful == service.metrics().edge_work
    assert all(0 < a["useful"] <= a["slots"] - a["pad"]
               for _, a in _counts(found))


def test_results_bit_identical_with_tracing_on_and_off(tmp_path):
    store_off, store_on = _store(), _store()
    plan = optimal_plan(store_off)
    off = run_plan_batched(store_off, plan, SSSP, 0)
    on, _ = _traced(tmp_path / "plan",
                    lambda: run_plan_batched(store_on, plan, SSSP, 0))
    assert off.results.keys() == on.results.keys()
    for snap in off.results:
        np.testing.assert_array_equal(np.asarray(off.results[snap]),
                                      np.asarray(on.results[snap]))
    assert (off.stable_milli, off.base_stats.sweeps) == (
        on.stable_milli, on.base_stats.sweeps)

    _, served_off = _serve(_store())
    (_, served_on), _ = _traced(tmp_path / "serve", lambda: _serve(_store()))
    assert served_off.keys() == served_on.keys()
    for key in served_off:
        np.testing.assert_array_equal(np.asarray(served_off[key]),
                                      np.asarray(served_on[key]))


def test_the_chip_benchmark_names_this_module_and_these_spans(monkeypatch):
    # chipbench/spans.py takes a trace with no cg. span and no module of
    # this name for a program that predates the spans, and then reads
    # nothing: moving the module must fail here, not silence its readers.
    path = pathlib.Path(__file__).parents[1] / "chipbench" / "spans.py"
    spec = importlib.util.spec_from_file_location("chipbench_spans", path)
    bench = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, spec.name, bench)
    spec.loader.exec_module(bench)
    assert importlib.import_module(bench.PROGRAM) is spans
    assert bench.FIXPOINT == ("cg.launch", "cg.anchor.")
    assert all(name.startswith(bench.FIXPOINT) for name in FIXPOINT)
    assert (bench.STORE, bench.LAUNCH) == ("cg.store.", "cg.launch")
