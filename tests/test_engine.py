"""Engine invariants: incremental == from-scratch, gating/no-parent exactness,
batched executor equivalence (unit + hypothesis property tests), and the
sweep's shape: one gather per edge slot, bit-identical to the two-gather form."""

import collections
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import SnapshotStore
from repro.graph import (
    incremental_additions,
    incremental_additions_batched,
    make_evolving_sequence,
    run_to_fixpoint,
)
from repro.graph import engine
from repro.graph.edgeset import EdgeBlock, EdgeView, keys_to_edges, make_block
from repro.graph.semiring import ALL_SEMIRINGS, SSSP, VITERBI


@st.composite
def evolving(draw):
    n = draw(st.integers(30, 120))
    e = draw(st.integers(40, 400))
    snaps = draw(st.integers(2, 5))
    changes = draw(st.integers(2, 30)) * 2
    seed = draw(st.integers(0, 2**16))
    return n, e, snaps, changes, seed


@given(params=evolving(), alg=st.sampled_from(list(ALL_SEMIRINGS)))
@settings(max_examples=10, deadline=None)
def test_incremental_additions_reach_scratch_fixpoint(params, alg):
    """Property: warm-start + Δ additions converges to the exact from-scratch
    fixpoint (the monotonicity argument the whole paper rests on)."""
    n, e, snaps, changes, seed = params
    sr = ALL_SEMIRINGS[alg]
    seq = make_evolving_sequence(n, e, snaps, changes, seed=seed)
    store = SnapshotStore(seq, granule=64)
    window = (0, snaps - 1)
    cg = store.common_graph_view(*window)
    base = run_to_fixpoint(cg, sr, 0)
    for i in range(snaps):
        delta = store.delta_block(window, (i, i))
        view = cg.extended(delta)
        inc = incremental_additions(view, delta, sr, base.values, base.parent)
        ref = run_to_fixpoint(store.snapshot_view(i), sr, 0)
        np.testing.assert_allclose(np.asarray(inc.values), np.asarray(ref.values),
                                   rtol=1e-6)


@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("track_parents", [False, True])
def test_modes_are_exact(gated, track_parents):
    seq = make_evolving_sequence(300, 2500, 4, 150, seed=5)
    store = SnapshotStore(seq, granule=128)
    for alg in ("sssp", "viterbi"):
        sr = ALL_SEMIRINGS[alg]
        ref = run_to_fixpoint(store.snapshot_view(1), sr, 0)
        view = (store.window_view_split(1, 1, 4) if gated
                else store.snapshot_view(1))
        got = run_to_fixpoint(view, sr, 0, gated=gated,
                              track_parents=track_parents)
        np.testing.assert_allclose(np.asarray(got.values), np.asarray(ref.values))
        if track_parents and not gated:
            np.testing.assert_array_equal(np.asarray(got.parent),
                                          np.asarray(ref.parent))


def test_batched_equals_sequential():
    seq = make_evolving_sequence(250, 2000, 5, 120, seed=9)
    store = SnapshotStore(seq, granule=128)
    sr = SSSP
    window = (0, 4)
    cg = store.common_graph_view(*window)
    base = run_to_fixpoint(cg, sr, 0)
    deltas = [store.delta_keys(window, (i, i)) for i in range(5)]
    e_max = max(d.shape[0] for d in deltas)
    srcs, dsts, ws = [], [], []
    for dk in deltas:
        s, d = keys_to_edges(dk, store.num_nodes)
        blk = make_block(s, d, seq.weights_for(dk), store.num_nodes,
                         granule=max(e_max, 1))
        srcs.append(blk.src); dsts.append(blk.dst); ws.append(blk.w)
    stacked = EdgeBlock(jnp.stack(srcs), jnp.stack(dsts), jnp.stack(ws))
    values = jnp.broadcast_to(base.values, (5, store.num_nodes))
    parent = jnp.broadcast_to(base.parent, (5, store.num_nodes))
    res = incremental_additions_batched(store.num_nodes, sr, values, parent,
                                        tuple(cg.blocks), (stacked,))
    for i in range(5):
        ref = run_to_fixpoint(store.snapshot_view(i), sr, 0)
        np.testing.assert_allclose(np.asarray(res.values[i]),
                                   np.asarray(ref.values), rtol=1e-6)


def test_view_block_sharing_is_zero_copy():
    """The mutation-free representation: extended views share block objects."""
    seq = make_evolving_sequence(100, 600, 3, 40, seed=2)
    store = SnapshotStore(seq, granule=64)
    cg = store.common_graph_view()
    d0 = store.delta_block((0, 2), (0, 0))
    v0 = cg.extended(d0)
    v1 = cg.extended(store.delta_block((0, 2), (1, 1)))
    assert v0.blocks[0] is cg.blocks[0] and v1.blocks[0] is cg.blocks[0]
    assert store.delta_block((0, 2), (0, 0)) is d0  # cached, not rebuilt


def test_edge_work_counts_frontier_masked_edges():
    seq = make_evolving_sequence(200, 1500, 2, 80, seed=3)
    store = SnapshotStore(seq, granule=128)
    full = run_to_fixpoint(store.snapshot_view(0), SSSP, 0)
    # warm re-run from the fixpoint: nothing improves — one no-op sweep at most
    again = run_to_fixpoint(store.snapshot_view(0), SSSP, 0,
                            values=full.values, parent=full.parent)
    assert int(again.iterations) <= 1


def test_make_block_orders_edges_by_a_stable_dst_sort():
    """Blocks keep the order of a stable sort by dst: ties keep input order."""
    rng = np.random.default_rng(4)
    src = rng.integers(0, 50, 700).astype(np.int32)
    dst = rng.integers(0, 50, 700).astype(np.int32)   # many ties
    w = rng.random(700).astype(np.float32)
    blk = make_block(src, dst, w, 50, granule=256)
    order = np.argsort(dst, kind="stable")
    np.testing.assert_array_equal(np.asarray(blk.src[:700]), src[order])
    np.testing.assert_array_equal(np.asarray(blk.dst[:700]), dst[order])
    np.testing.assert_array_equal(np.asarray(blk.w[:700]), w[order])


# ---------------------------------------------------------------------------
# One gather per edge slot per sweep: the lowered loop's shape, and exactness
# against the two-gather sweep (frontier[src] and values[src]) it replaced.
# ---------------------------------------------------------------------------

def _functions(text):
    """Lowered StableHLO text -> {function name: its body lines}."""
    funcs, name = {}, None
    for line in text.splitlines():
        m = re.match(r"  func\.func \w+ @(\w+)", line)
        if m:
            name = m.group(1)
            funcs[name] = []
        elif line == "  }":
            name = None
        elif name is not None:
            funcs[name].append(line)
    return funcs


def _op_counts(lines, funcs):
    """Gathers (and those of a bool operand) and scatters by reducer, with
    called functions counted where they are called."""
    c = collections.Counter()
    for i, line in enumerate(lines):
        if '"stablehlo.gather"' in line:
            c["gather"] += 1
            if re.search(r"\) : \(tensor<[0-9x]*i1>", line):
                c["gather_pred"] += 1
        if '"stablehlo.scatter"' in line:
            c["scatter_" + re.search(r"stablehlo\.(\w+)", lines[i + 2])[1]] += 1
        m = re.search(r"func\.call @(\w+)", line)
        if m:
            c += _op_counts(funcs[m[1]], funcs)
    return c


def _loop_split(text):
    """(ops inside the one while loop, ops of main outside it)."""
    funcs = _functions(text)
    main = funcs["main"]
    starts = [i for i, line in enumerate(main) if "stablehlo.while(" in line]
    assert len(starts) == 1, "expected exactly one sweep loop"
    indent = " " * (len(main[starts[0]]) - len(main[starts[0]].lstrip()))
    do = main.index(indent + "} do {", starts[0])
    end = main.index(indent + "}", do)
    return (_op_counts(main[do + 1:end], funcs),
            _op_counts(main[:starts[0]] + main[end + 1:], funcs))


def _block(rng, n, e):
    return make_block(rng.integers(0, n, e).astype(np.int32),
                      rng.integers(0, n, e).astype(np.int32),
                      rng.uniform(0.001, 1.0, e).astype(np.float32), n,
                      granule=128)


def _stacked(rng, n, e, lanes):
    blks = [_block(rng, n, e) for _ in range(lanes)]
    return EdgeBlock(*(jnp.stack(a) for a in zip(*blks)))


@pytest.mark.parametrize("track_parents", [False, True])
@pytest.mark.parametrize("launch", ["fixpoint_one_block", "fixpoint_cg_delta",
                                    "batched_stacked_delta",
                                    "batched_cg_stacked_delta"])
def test_sweep_loop_gathers_once_per_block(launch, track_parents):
    """The lowered fixpoint loop gathers once per block (twice with parents:
    the winner test reads best[dst]), never the bool frontier, scatters once
    per block per reduction, and the out-degree segment_sum is hoisted."""
    n, lanes = 64, 4
    rng = np.random.default_rng(7)
    sr = SSSP
    if launch.startswith("fixpoint"):
        blocks = ((_block(rng, n, 300),) if launch == "fixpoint_one_block"
                  else (_block(rng, n, 300), _block(rng, n, 40)))
        values = engine.init_values(n, sr, 0)
        parent = jnp.full((n,), -1, jnp.int32)
        frontier = jnp.zeros((n,), bool).at[0].set(True)
        text = engine._fixpoint_jit.lower(
            sr, n, 100, values, parent, frontier, blocks, False,
            track_parents, 1).as_text()
    else:
        shared = (() if launch == "batched_stacked_delta"
                  else (_block(rng, n, 300),))
        deltas = (_stacked(rng, n, 40, lanes),)
        blocks = shared + deltas
        values = jnp.broadcast_to(engine.init_values(n, sr, 0), (lanes, n))
        parent = jnp.full((lanes, n), -1, jnp.int32)
        text = engine._batched_incremental_jit.lower(
            sr, n, 100, values, parent, shared, deltas, track_parents,
            False, None, None, "instability", 1).as_text()
    inside, outside = _loop_split(text)
    per_block = 2 if track_parents else 1
    assert inside["gather"] == per_block * len(blocks)
    assert inside["gather_pred"] == 0
    assert inside["scatter_minimum"] == per_block * len(blocks)
    assert inside["scatter_add"] == 0
    assert outside["scatter_add"] >= len(blocks)


def _old_block_sweep(sr, n, values, frontier, src, dst, w, track_parents):
    """The two-gather sweep of one block, as the engine ran it before."""
    ident = jnp.float32(sr.identity)
    active = frontier[src]
    cand = jnp.where(active, sr.combine(values[src], w), ident)
    blk_best = engine._segment_reduce(sr, cand, dst, n + 1)[:n]
    work = jnp.sum(active & (dst < n), dtype=jnp.float32)
    if not track_parents:
        return blk_best, None, work
    best_pad = jnp.concatenate([blk_best, jnp.float32([ident])])
    is_win = active & (cand == best_pad[dst])
    parent_cand = jnp.where(is_win, src, engine.INT_MAX)
    blk_winner = jax.ops.segment_min(parent_cand, dst, n + 1)[:n]
    return blk_best, blk_winner, work


def _old_sweep(sr, n, values, parent, frontier, blocks, track_parents):
    best = jnp.full((n,), jnp.float32(sr.identity))
    bests, work = [], jnp.float32(0)
    for src, dst, w in blocks:
        blk_best, blk_winner, dw = _old_block_sweep(
            sr, n, values, frontier, src, dst, w, track_parents)
        best = sr.better(best, blk_best)
        bests.append((blk_best, blk_winner))
        work = work + dw
    improved = sr.strictly_better(best, values)
    new_values = sr.better(values, best)
    if track_parents:
        winner = jnp.full((n,), engine.INT_MAX, dtype=jnp.int32)
        for blk_best, blk_winner in bests:
            winner = jnp.where(blk_best == best,
                               jnp.minimum(winner, blk_winner), winner)
        parent = jnp.where(improved, winner, parent)
    return new_values, parent, improved, work


@functools.partial(jax.jit, static_argnums=(0, 1, 6, 7))
def _old_run(sr, n, values, parent, frontier, blocks, track_parents,
             seed_mode, seed_blocks):
    """Seed sweep (if ``seed_mode``) plus fixpoint, all two-gather sweeps."""
    it0 = work0 = unstable = None
    if seed_mode is not None:
        seed_frontier = (values != jnp.float32(sr.identity)
                         if seed_mode == "instability"
                         else jnp.ones((n,), bool))
        values, parent, frontier, work0 = _old_sweep(
            sr, n, values, parent, seed_frontier, seed_blocks, track_parents)
        it0, unstable = 1, jnp.sum(frontier, dtype=jnp.int32)

    def body(state):
        values, parent, frontier, it, work = state
        values, parent, improved, dw = _old_sweep(
            sr, n, values, parent, frontier, blocks, track_parents)
        return values, parent, improved, it + 1, work + dw

    values, parent, _, it, work = jax.lax.while_loop(
        lambda s: jnp.logical_and(s[3] < 10_000, jnp.any(s[2])), body,
        (values, parent, frontier, jnp.int32(0), jnp.float32(0)))
    if seed_mode is not None:
        it, work = it + it0, work + work0
    return values, parent, it, work, unstable


def _exactness_graph(case):
    """(common-graph view, Δ block, source). The underflow case hangs a
    chain of 1e-30 weights off the source on vertices the rest cannot reach,
    so Viterbi products fall to 0 (the identity) on vertices that the warm
    start's frontier and the Δ edges leaving them then hold."""
    n, rng = 200, np.random.default_rng(11)
    if case != "viterbi_underflow":
        cg, source, delta_src = [_block(rng, n, 1500)], 0, np.arange(0, 8)
    else:
        chain = np.arange(188, 200, dtype=np.int32)
        src = np.concatenate([rng.integers(0, 188, 1500), chain[:-1], [188]])
        dst = np.concatenate([rng.integers(0, 188, 1500), chain[1:], [0]])
        w = np.concatenate([rng.uniform(0.001, 1.0, 1500),
                            np.full(11, 1e-30), [0.5]])
        cg = [make_block(src.astype(np.int32), dst.astype(np.int32),
                         w.astype(np.float32), n, granule=128)]
        source, delta_src = 188, chain[2:10]
    src = rng.integers(0, n, 120).astype(np.int32)
    src[:8] = delta_src
    delta = make_block(src, rng.integers(0, n, 120).astype(np.int32),
                       rng.uniform(0.001, 1.0, 120).astype(np.float32), n,
                       granule=128)
    return EdgeView(tuple(cg), n), delta, source


@pytest.mark.parametrize("track_parents", [False, True])
@pytest.mark.parametrize("start", ["fresh", "warm", "delta", "instability"])
@pytest.mark.parametrize("case", [*ALL_SEMIRINGS, "viterbi_underflow"])
def test_one_gather_sweep_is_bit_identical_to_two_gather(case, start,
                                                         track_parents):
    sr = VITERBI if case == "viterbi_underflow" else ALL_SEMIRINGS[case]
    cg, delta, source = _exactness_graph(case)
    n = cg.num_nodes
    view = cg.extended(delta)
    base = run_to_fixpoint(cg, sr, source)
    if case == "viterbi_underflow":
        assert int(jnp.sum(base.values[190:] == 0)) == 10
    seed_mode, seed_blocks = None, ()
    if start == "fresh":
        new = run_to_fixpoint(view, sr, source, track_parents=track_parents)
        values = engine.init_values(n, sr, source)
        parent = jnp.full((n,), engine.NO_PARENT, jnp.int32)
        frontier = jnp.zeros((n,), bool).at[source].set(True)
    elif start == "warm":
        new = run_to_fixpoint(view, sr, source, values=base.values,
                              parent=base.parent, track_parents=track_parents)
        values, parent = base.values, base.parent
        frontier = jnp.ones((n,), bool)
    else:
        new = incremental_additions(view, delta, sr, base.values, base.parent,
                                    track_parents=track_parents, seed=start)
        values, parent, frontier = base.values, base.parent, None
        seed_mode, seed_blocks = start, (delta,)
    old_values, old_parent, old_it, old_work, old_unstable = _old_run(
        sr, n, values, parent, frontier, tuple(view.blocks), track_parents,
        seed_mode, seed_blocks)
    np.testing.assert_array_equal(np.asarray(new.values),
                                  np.asarray(old_values))
    np.testing.assert_array_equal(np.asarray(new.parent),
                                  np.asarray(old_parent))
    assert int(new.iterations) == int(old_it)
    assert float(new.edge_work) == float(old_work)
    if seed_mode is None:
        assert new.unstable is None
    else:
        assert int(new.unstable) == int(old_unstable)
