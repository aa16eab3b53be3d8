"""The store's Δ key memo (``SnapshotStore.delta_keys``).

Each nested (parent, child) hop's addition keys are computed once and
returned, read-only, on every later call. The memo changes no value: every
Δ and every stacked block equals the one a fresh store computes. Cache hits
open no ``cg.store.keys`` span; a ``delta_stack`` miss counts its lanes
served from the memo (``delta_hit``) and computed (``delta_miss``);
``compact`` drops the entries that depend on retired snapshots.
"""

import numpy as np
import pytest
from test_spans import _traced

from repro.core import (
    EdgeLog,
    LiveSequence,
    SnapshotStore,
    Watermark,
    events_from_sequence,
    replay_events,
)
from repro.graph import make_evolving_sequence

SNAPS = 5
WINDOWS = [(i, j) for i in range(SNAPS) for j in range(i, SNAPS)]


def _seq(snaps=SNAPS):
    return make_evolving_sequence(300, 2400, snaps, 150, seed=11)


def _store():
    return SnapshotStore(_seq(), granule=128)


def _nested(parent):
    return [c for c in WINDOWS if parent[0] <= c[0] and c[1] <= parent[1]]


def _arrays(blk):
    return [np.asarray(a) for a in blk]


@pytest.mark.parametrize("parent", WINDOWS)
def test_memoised_delta_keys_equal_a_fresh_difference(parent):
    store, fresh = _store(), _store()
    for child in _nested(parent):
        keys = store.delta_keys(parent, child)
        expected = np.setdiff1d(fresh.window_keys(*child),
                                fresh.window_keys(*parent), assume_unique=True)
        np.testing.assert_array_equal(keys, expected)
        assert store.delta_keys(list(parent), list(child)) is keys
        assert not keys.flags.writeable
        with pytest.raises(ValueError):
            keys[:1] = 0


def test_a_non_nested_pair_still_raises_after_its_windows_are_cached():
    store = _store()
    store.delta_keys((0, 4), (1, 3))
    with pytest.raises(ValueError, match="not nested"):
        store.delta_keys((1, 3), (0, 4))


def test_a_memo_hit_opens_no_keys_span(tmp_path):
    store = _store()
    store.window_keys(0, 4), store.window_keys(1, 2)    # intersections cached
    (first, second), found = _traced(tmp_path, lambda: (
        store.delta_keys((0, 4), (1, 2)), store.delta_keys((0, 4), (1, 2))))
    assert first is second
    assert [s[2] for s in found].count("cg.store.keys") == 1


@pytest.mark.parametrize("stack", ["delta_stack", "slide_stack"])
def test_stacked_blocks_are_bit_identical_to_a_fresh_stores(stack):
    hops = [((0, 4), (0, 1)), ((0, 4), (1, 3)), ((1, 3), (2, 2)),
            ((0, 4), (1, 3))]
    windows = [(0, 1), (1, 2), (2, 3), (3, 4)]

    def build(store):
        if stack == "delta_stack":
            return store.delta_stack(hops, num_lanes=8)
        return store.slide_stack(windows, num_lanes=8)

    store = _store()
    for parent, child in hops + [((0, 4), w) for w in windows]:
        store.delta_keys(parent, child)                 # warm the memo
    for got, want in zip(_arrays(build(store)), _arrays(build(_store()))):
        np.testing.assert_array_equal(got, want)
    store.release()                                     # rebuilt from the memo
    for got, want in zip(_arrays(build(store)), _arrays(build(_store()))):
        np.testing.assert_array_equal(got, want)


def test_a_stack_miss_counts_memo_hits_and_misses_per_real_lane(tmp_path):
    store = _store()
    store.delta_keys((0, 4), (0, 1))
    store.window_keys(2, 3)                             # intersections cached
    hops = [((0, 4), (0, 1)), ((0, 4), (2, 3)), ((0, 4), (2, 3))]
    _, found = _traced(tmp_path / "miss", lambda: store.delta_stack(
        hops, num_lanes=4))
    counted = [s[4] for s in found if s[2] == "cg.store.blocks"]
    assert counted == [{"delta_hit": 2, "delta_miss": 1}]
    assert sum(counted[0].values()) == len(hops)        # not the 4-lane bucket
    assert [s[2] for s in found].count("cg.store.keys") == 1
    _, found = _traced(tmp_path / "hit", lambda: store.delta_stack(
        hops, num_lanes=4))
    assert found == []                                  # a "DS" hit: no span


def test_compact_drops_the_memo_entries_of_retired_snapshots():
    seq = _seq(snaps=6)
    store = SnapshotStore(LiveSequence(seq.num_nodes,
                                       weight_seed=seq.weight_seed))
    log = EdgeLog(seq.num_nodes)
    wm = Watermark(log, store)
    replay_events(log, wm, events_from_sequence(seq))
    live = [(i, j) for i in range(6) for j in range(i, 6)]
    for parent in live:
        for child in live:
            if parent[0] <= child[0] and child[1] <= parent[1]:
                store.delta_keys(parent, child)
    kept = store.delta_keys((2, 5), (3, 4))
    before = store.stored_edges
    stats = store.compact(before=2)
    assert stats.retired == 2 and store.first_live == 2
    assert store.stored_edges < before
    assert store._deltas and all(parent[0] >= 2 for parent, _ in store._deltas)
    assert store.delta_keys((2, 5), (3, 4)) is kept
    with pytest.raises(ValueError, match="retired"):
        store.delta_keys((1, 5), (3, 4))
