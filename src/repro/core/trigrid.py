"""Triangular Grid (TG) work-sharing scheduler (paper §2, second contribution).

TG node T(i,j) = common graph of snapshots i..j; apex = T(0,n−1) = the
CommonGraph; leaves = the snapshots. Descending a grid edge only *adds*
edges, and because nested windows give nested common graphs the addition
volume of any hop (i,j)→(a,b) is exactly |T(a,b)| − |T(i,j)| — so optimal
work sharing over the grid is a clean interval DP:

    cost(i,j) = 0                                  if i == j
    cost(i,j) = min_m  (|T(i,m)| − |T(i,j)|) + cost(i,m)
                     + (|T(m+1,j)| − |T(i,j)|) + cost(m+1,j)

The paper explores the grid with red-arrow schedules; the DP finds the
edge-volume-optimal schedule among all direct hops in the grid (one-level
descents are the m∈{i, j−1} cases, so the paper's schedules are in the DP's
search space). A balanced-bisection plan is provided as the simple heuristic
for comparison; Direct-Hop is the degenerate star plan.

Execution walks the plan tree: each node's state hops from its parent state
via the addition-only incremental engine; each node's edge view = parent's
view ⊕ one Δ block (immutable, shared — zero mutation). Sibling subtrees are
*independent* — the per-level batched executor stacks them on a snapshot
axis (paper's parallelism claim; sharded over `data` on a mesh).

Executor contract (both ``run_plan`` and ``run_plan_batched``):

* **Bit-identical results.** For the same plan, semiring, source and
  options, the batched executor returns bit-identical values (and parents,
  when tracked) to the sequential DFS, which in turn matches the
  per-snapshot from-scratch fixpoint up to float tolerance. Each batched
  lane converges over exactly the edge set the sequential executor would
  use (apex blocks + the lane's cumulative Δ + the hop Δ), and the monotone
  fixpoint is order-free — tests/test_trigrid_batched.py enforces this.
* **Shape-bucketing invariant.** Batched levels consume
  ``SnapshotStore.delta_stack`` buffers whose stacked shape is ``(pow2 lane
  bucket, pow2 width bucket)`` — never the exact lane count or ragged Δ
  sizes. The lane axis pads to ``lane_bucket(lanes, data_extent)`` with
  trailing *masked* lanes (all-sentinel Δ, parent-state copy, frontier
  never seeded, ``lane_valid=False``), so the number of distinct jit traces
  stays bounded by bucket combinations across ALL plans, and every level
  divides the mesh's ``data`` axis.
* **Always sharded on a mesh.** With ``mesh=`` given, every level's lane
  axis shards over ``data`` — lane bucketing removed the old
  replicated-execution fallback (and its UserWarning) entirely.
* **Work accounting.** Padding edges never count toward ``edge_work``, and
  masked padding lanes are zeroed out of ``edge_work``/``iterations``; the
  batched seed relaxes only the final parent→child hop Δ (``seed_blocks``),
  so per-plan total edge work equals the sequential executor's.

The sliding-window executor (core/window.py) reuses this machinery with
windows instead of plan levels and inherits the same contract.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kickstarter import StreamStats
from repro.core.snapshots import SnapshotStore
from repro.graph.edgeset import EdgeBlock, EdgeView, lane_bucket
from repro.graph.engine import (
    gather_lane_states,
    host_sync,
    incremental_additions,
    incremental_additions_batched,
    run_to_fixpoint,
)
from repro.graph.semiring import Semiring
from repro.graph.stability import stable_fraction_milli
from repro.runtime.spans import span

Window = tuple[int, int]


@dataclasses.dataclass
class PlanNode:
    """A Triangular-Grid plan-tree node: a window plus its child hops.

    Every edge of the tree is an addition-only hop T(parent) → T(child)
    (nesting guarantees Δ ≥ 0); the root is the plan's apex window.
    """

    window: Window
    children: list["PlanNode"]

    def leaves(self) -> list[Window]:
        """The plan's leaf windows in DFS order (the answered snapshots)."""
        if not self.children:
            return [self.window]
        out = []
        for c in self.children:
            out.extend(c.leaves())
        return out


def hop_added_edges(store: SnapshotStore, parent: Window, child: Window) -> int:
    """Δ-edge volume of the grid hop T(parent) → T(child).

    Nested windows give nested common graphs, so the hop streams exactly
    ``|T(child)| − |T(parent)|`` addition edges — the ONE cost atom every
    Δ-volume optimizer in the repo is built from: ``optimal_plan``'s
    interval DP over hops, ``plan_added_edges`` accounting, and the
    campaign planner's DP over window partitions
    (core/window.py::optimal_campaigns).
    """
    return store.window_size(*child) - store.window_size(*parent)


def optimal_plan(store: SnapshotStore, i: int = 0, j: int | None = None,
                 cost_model=None) -> PlanNode:
    """Interval-DP plan minimizing total hop cost.

    Without ``cost_model`` a hop's price is its raw added-edge volume (the
    paper's objective). With a calibrated :class:`~repro.core.costmodel.
    SweepCostModel` each hop is priced by ``cost_model.hop_cost(Δ)`` — the
    measured affine per-edge + per-sweep cost (with the stable-vertex
    discount folded in), so the DP trades hop count against Δ volume the
    way the machine actually charges for them. Either way the DP is exact
    over integer prices.

    Bottom-up over interval spans (and an explicit-stack tree build), so
    neither the DP nor a maximally skewed optimal plan can hit Python's
    recursion limit on long snapshot sequences.
    """
    if j is None:
        j = store.seq.num_snapshots - 1
    size = store.window_size  # cached |T(a,b)|
    price = (cost_model.hop_cost if cost_model is not None
             else (lambda added: added))

    cost: dict[Window, int] = {(a, a): 0 for a in range(i, j + 1)}
    split: dict[Window, int] = {}
    for span in range(1, j - i + 1):
        for a in range(i, j + 1 - span):
            b = a + span
            s_ab = size(a, b)
            best, arg = None, a
            for m in range(a, b):
                c = (price(size(a, m) - s_ab) + cost[(a, m)]
                     + price(size(m + 1, b) - s_ab) + cost[(m + 1, b)])
                if best is None or c < best:
                    best, arg = c, m
            cost[(a, b)] = best
            split[(a, b)] = arg

    root = PlanNode((i, j), [])
    stack = [root]
    while stack:
        node = stack.pop()
        a, b = node.window
        if a == b:
            continue
        m = split[(a, b)]
        node.children = [PlanNode((a, m), []), PlanNode((m + 1, b), [])]
        stack.extend(node.children)
    return root


def _resolve_last(j: int | None, n: int | None) -> int:
    if j is None:
        if n is None:
            raise ValueError("pass either j= or n=")
        j = n - 1
    return j


def bisection_plan(i: int = 0, j: int | None = None, *, n: int | None = None) -> PlanNode:
    """Balanced bisection heuristic (no size table needed)."""
    j = _resolve_last(j, n)
    def build(a: int, b: int) -> PlanNode:
        if a == b:
            return PlanNode((a, b), [])
        m = (a + b) // 2
        return PlanNode((a, b), [build(a, m), build(m + 1, b)])
    return build(i, j)


def direct_hop_plan(i: int = 0, j: int | None = None, *, n: int | None = None) -> PlanNode:
    """The paper's star schedule: every snapshot one hop from the apex."""
    j = _resolve_last(j, n)
    return PlanNode((i, j), [PlanNode((k, k), []) for k in range(i, j + 1)]) \
        if i != j else PlanNode((i, i), [])


def plan_added_edges(store: SnapshotStore, plan: PlanNode) -> int:
    """Total Δ-edge volume streamed by a plan (excludes the apex itself)."""
    total = 0
    def walk(node: PlanNode):
        nonlocal total
        for c in node.children:
            total += hop_added_edges(store, node.window, c.window)
            walk(c)
    walk(plan)
    return total


@dataclasses.dataclass
class WorkSharingRun:
    """Result record of a TG plan execution: per-snapshot values plus the
    apex fixpoint stats, per-hop stats and timing/Δ-volume/lane accounting
    the work-sharing benchmarks compare executors by."""

    results: dict[int, jnp.ndarray]   # snapshot index -> values
    base_stats: StreamStats
    hop_stats: list[StreamStats]
    wall_s: float
    added_edges: int
    # (valid lanes, lane_bucket) per batched launch — what actually ran,
    # for lanes-per-device / padding reporting. Empty on sequential runs.
    lane_layout: "list[tuple[int, int]]" = dataclasses.field(
        default_factory=list)
    # measured stable fraction (‰) over all plan hops: the share of
    # vertex-lanes the stability analysis kept out of the seed frontier
    # (graph/stability.py; padding lanes excluded)
    stable_milli: int = 0


def _anchor_view(store, window, cg_split):
    """The anchor window's edge view, split per ``cg_split``.

    The ONE place the split policy lives: the TG/window anchor rebuilds and
    the streaming scheduler's cache-hit/cover paths (core/window.py) all
    route through here, so hit/hop/rebuild views can never diverge.
    """
    return (store.window_view_split(*window, cg_split) if cg_split > 1
            else store.common_graph_view(*window))


def _sweep_counts(stats: StreamStats, bucket: int, blocks, real: int,
                  seed_blocks=None, seed_real: int = 0) -> dict:
    """The counters of a fixpoint span: ``useful``, ``slots`` and ``pad``.

    ``useful`` is the launch's ``edge_work``: relaxations from frontier
    vertices over real edges, the seed sweep included. ``slots`` is the
    edge slots its sweeps executed, from the block shapes and the fetched
    sweep count: the seed sweep (when ``seed_blocks`` is given; it touches
    only those blocks) plus ``stats.sweeps`` fixpoint sweeps (one fewer
    when seeded) over ``blocks``. Under ``vmap`` every lane of the
    ``bucket`` runs to the slowest lane's count, masked padding lanes
    included, and fused chunks run the same count. With ``gated=True`` a
    skipped block still counts, so ``slots`` is an upper bound there.
    ``pad`` is the sentinel slots among ``slots``; ``real`` and
    ``seed_real`` are the real edges per sweep over all lanes.

    Host arithmetic on shapes and on numbers already copied back: no
    device transfer. ``slots`` is therefore a model of today's dense sweep
    (``engine._block_sweep`` reads every slot of every block each sweep),
    not a count the device keeps: a change to which slots a sweep reads
    changes this function in the same change.
    """
    def width(blks) -> int:   # a shared block is swept once per lane
        return bucket * sum(int(b.src.shape[-1]) for b in blks)

    sweeps = stats.sweeps
    slots = pad = 0
    if seed_blocks is not None:
        sweeps -= 1
        slots = width(seed_blocks)
        pad = slots - seed_real
    slots += sweeps * width(blocks)
    pad += sweeps * (width(blocks) - real)
    return {"useful": int(stats.edge_work), "slots": slots, "pad": pad}


def _anchor_base(store, window, semiring, source, max_iters, gated, cg_split,
                 track_parents, fused_k=1):
    """Anchor-window fixpoint shared by all executors: (view, result, stats).

    The TG executors anchor at the plan apex; the sliding-window executors
    (core/window.py) anchor at the windows' common super-window. The span
    ``cg.anchor.rebuild`` closes after the sync, so it holds the device
    work.
    """
    t0 = time.perf_counter()
    with span("cg.anchor.rebuild") as sp:
        apex_view = _anchor_view(store, window, cg_split)
        base = run_to_fixpoint(apex_view, semiring, source, max_iters,
                               gated=gated, track_parents=track_parents,
                               fused_k=fused_k)
        host_sync(base.values)
        base_stats = StreamStats(time.perf_counter() - t0,
                                 float(base.edge_work), int(base.iterations))
        if sp.live:
            sp.set(**_sweep_counts(base_stats, 1, apex_view.blocks,
                                   store.window_size(*window)))
    return apex_view, base, base_stats


def run_plan(
    store: SnapshotStore,
    plan: PlanNode,
    semiring: Semiring,
    source: int,
    max_iters: int = 10_000,
    gated: bool = False,
    cg_split: int = 1,
    track_parents: bool = False,
    seed: str = "instability",
    fused_k: int = 1,
) -> WorkSharingRun:
    """Execute a TG plan (DFS; each hop = addition-only incremental update).

    ``fused_k`` threads to the engine's fused-chunk launch option
    (bit-identical results at any value; see engine.relax_sweep_fused).
    """
    t_all = time.perf_counter()
    apex_view, base, base_stats = _anchor_base(
        store, plan.window, semiring, source, max_iters, gated, cg_split,
        track_parents, fused_k)

    results: dict[int, jnp.ndarray] = {}
    hop_stats: list[StreamStats] = []
    unstable_counts: list[int] = []

    def dfs(node: PlanNode, view: EdgeView, values, parent):
        if not node.children:
            results[node.window[0]] = values
            return
        for child in node.children:
            t0 = time.perf_counter()
            delta = store.delta_block(node.window, child.window)
            child_view = view.extended(delta)          # shared immutable blocks
            res = incremental_additions(child_view, delta, semiring,
                                        values, parent, max_iters, gated=gated,
                                        track_parents=track_parents, seed=seed,
                                        fused_k=fused_k)
            host_sync(res.values)
            hop_stats.append(StreamStats(time.perf_counter() - t0,
                                         float(res.edge_work),
                                         int(res.iterations)))
            unstable_counts.append(int(res.unstable))
            dfs(child, child_view, res.values, res.parent)

    dfs(plan, apex_view, base.values, base.parent)
    return WorkSharingRun(results, base_stats, hop_stats,
                          time.perf_counter() - t_all,
                          plan_added_edges(store, plan),
                          stable_milli=stable_fraction_milli(
                              unstable_counts, store.num_nodes))


def plan_levels(plan: PlanNode) -> list[list[tuple[int, PlanNode]]]:
    """Group plan nodes by depth: level d = [(parent lane index, node), ...].

    The parent lane index points into level d−1 (the apex is the single lane
    of level −1). All nodes at one depth are independent given their parents'
    states — the invariant the level-synchronous executor batches on.
    """
    levels: list[list[tuple[int, PlanNode]]] = []
    cur = [plan]
    while True:
        nxt = [(pi, c) for pi, node in enumerate(cur) for c in node.children]
        if not nxt:
            return levels
        levels.append(nxt)
        cur = [c for _, c in nxt]


def _shard_snapshot_axis(mesh, values, parent, blocks, lane_valid):
    """Place the lane (snapshot) axis over the mesh's ``data`` axis.

    Callers bucket the lane axis to a ``lane_bucket`` count (pow2, divisible
    by the ``data`` extent) before arriving here, so a mesh launch ALWAYS
    shards — there is no replicated fallback. ``lane_valid`` rides along so
    the mask is placed lane-aligned with the states it gates.
    """
    if mesh is None:
        return values, parent, blocks, lane_valid
    if values.shape[0] % mesh.shape["data"]:
        raise ValueError(
            f"lane axis of {values.shape[0]} does not divide the "
            f"{mesh.shape['data']}-device data axis — callers must bucket "
            "lane counts with lane_bucket() before sharding")
    row = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    values = jax.device_put(values, row)
    parent = jax.device_put(parent, row)
    lane_valid = jax.device_put(lane_valid, row)
    blocks = tuple(EdgeBlock(*(jax.device_put(a, row) for a in b))
                   for b in blocks)
    return values, parent, blocks, lane_valid


def run_plan_batched(
    store: SnapshotStore,
    plan: PlanNode,
    semiring: Semiring,
    source: int,
    max_iters: int = 10_000,
    gated: bool = False,
    cg_split: int = 1,
    track_parents: bool = False,
    mesh=None,
    seed: str = "instability",
    fused_k: int = 1,
) -> WorkSharingRun:
    """Execute a TG plan level-synchronously: one batched launch per depth.

    Siblings at the same depth of the plan tree are independent by
    construction, so each level runs as ONE ``incremental_additions_batched``
    launch: the level's ragged Δ-batches are stacked on a leading snapshot
    axis (``SnapshotStore.delta_stack``, shape-bucketed so jit traces stay
    bounded) and parent states are gathered into the lanes.

    Per-lane edge views are expressed as apex blocks (shared, broadcast) plus
    two stacked groups: the lane's *cumulative* Δ from the apex to its parent
    and the final parent→child hop Δ. For nested windows the cumulative Δ is
    exactly the union of the chain's per-hop Δs, so every lane re-converges
    over precisely the edge set the sequential executor would use — the
    monotone-fixpoint guarantee then makes the results bit-identical. The
    frontier is seeded from the hop Δ only (``seed_blocks``), matching the
    sequential seeding and its edge-work accounting.

    Each level's lane count pads to ``lane_bucket(lanes, data_extent)``:
    trailing masked lanes carry empty (all-sentinel) Δs and inert state
    copies, and only valid lanes are gathered back into ``results``. On a
    mesh the bucketed snapshot axis therefore ALWAYS shards over ``data``
    (see launch/evolve.py) — no lane count triggers replicated execution.

    ``gated`` stays exact here but buys no skip: inside vmap the block gate's
    ``lax.cond`` lowers to a select that relaxes every block for every lane.
    It is honored for the apex fixpoint (unbatched) and for result parity
    with the sequential executor, not as a batched-path speedup.
    """
    t_all = time.perf_counter()
    apex_view, base, base_stats = _anchor_base(
        store, plan.window, semiring, source, max_iters, gated, cg_split,
        track_parents, fused_k)

    results: dict[int, jnp.ndarray] = {}
    hop_stats: list[StreamStats] = []
    lane_layout: list[tuple[int, int]] = []
    unstable_counts: list = []
    if not plan.children:
        results[plan.window[0]] = base.values

    apex_window = plan.window
    n = store.num_nodes
    data_extent = mesh.shape["data"] if mesh is not None else 1
    prev_nodes = [plan]
    prev_values = base.values[None]
    prev_parent = base.parent[None]
    for level in plan_levels(plan):
        with span("cg.launch") as sp:
            t0 = time.perf_counter()
            lanes = len(level)
            bucket = lane_bucket(lanes, data_extent)
            lane_layout.append((lanes, bucket))
            hops = [(prev_nodes[pi].window, c.window) for pi, c in level]
            hop_stacked = store.delta_stack(hops, num_lanes=bucket)
            if any(parent != apex_window for parent, _ in hops):
                prefix_stacked = store.delta_stack(
                    [(apex_window, parent) for parent, _ in hops],
                    num_lanes=bucket)
                delta_blocks = (prefix_stacked, hop_stacked)
            else:
                delta_blocks = (hop_stacked,)   # level 1: parents ARE the apex

            # Masked padding lanes re-run lane 0's parent state over an
            # empty Δ: no frontier is ever seeded, values stay an inert
            # copy, and lane_valid zeroes them out of the work accounting.
            lane_map = [pi for pi, _ in level] + [0] * (bucket - lanes)
            values, parent = gather_lane_states(prev_values, prev_parent,
                                                lane_map)
            lane_valid = jnp.arange(bucket) < lanes
            values, parent, delta_blocks, lane_valid = _shard_snapshot_axis(
                mesh, values, parent, delta_blocks, lane_valid)
            res = incremental_additions_batched(
                n, semiring, values, parent,
                shared_blocks=tuple(apex_view.blocks),
                delta_blocks=delta_blocks, max_iters=max_iters,
                track_parents=track_parents, gated=gated,
                seed_blocks=(delta_blocks[-1],), lane_valid=lane_valid,
                seed=seed, fused_k=fused_k)
            host_sync(res.values)
            stats = StreamStats(time.perf_counter() - t0,
                                float(jnp.sum(res.edge_work)),
                                int(jnp.max(res.iterations)))
            if sp.live:
                size = store.window_size
                apex = size(*apex_window)
                sp.set(**_sweep_counts(
                    stats, bucket, apex_view.blocks + delta_blocks,
                    bucket * apex + sum(size(*c) - apex for _, c in hops),
                    (delta_blocks[-1],),
                    sum(size(*c) - size(*p) for p, c in hops)))
        hop_stats.append(stats)
        unstable_counts.extend(np.asarray(res.unstable)[:lanes])
        for lane, (_, c) in enumerate(level):
            if not c.children:
                results[c.window[0]] = res.values[lane]
        prev_nodes = [c for _, c in level]
        prev_values, prev_parent = res.values, res.parent

    return WorkSharingRun(results, base_stats, hop_stats,
                          time.perf_counter() - t_all,
                          plan_added_edges(store, plan), lane_layout,
                          stable_milli=stable_fraction_milli(
                              unstable_counts, store.num_nodes))
