"""Frontier-masked edge-relaxation fixpoint engine (TPU-native KickStarter core).

One *sweep* is a dense Bellman-Ford-style round over an edge view: mask
values by frontier (V elements), gather once, combine, segment-reduce.

    masked[u] = values[u] if frontier[u] else identity
    cand[e]   = combine(masked[src[e]], w[e])       (one gather per edge slot)
    best[v]   = segment_reduce(cand, dst)           (min or max semiring)
    values'   = meet(values, best);  frontier' = strictly-improved vertices

This is exactly the frontier-masked sweep: ``combine(identity, w) ==
identity`` for all five semirings, so an inactive edge's candidate is the
identity either way, and ``frontier[src]`` is never gathered. The edge work
(real edges whose source is in the frontier) is the frontier's dot product
with the view's real out-degree, which a launch computes once
(:func:`_out_degree`).

Monotone semirings make the dense sweep idempotent and order-free, which is
what lets us replace the CPU papers' per-vertex worklists + atomics with
segment reductions. ``parent[v]`` tracks the dependence edge
source that produced ``values[v]`` — the KickStarter trimming baseline
(core/kickstarter.py) consumes it on deletions.

The engine operates on *tuples of edge blocks* rather than one concatenated
array: a CommonGraph view is (CG block, Δ block, Δ block, …) and blocks are
physically shared between snapshots (the paper's mutation-free
representation executes as-is — no concatenation copies, and jit traces are
keyed only on the tuple of block shapes). Everything is shape-static and
jit/vmap/pjit-friendly: the snapshot axis of the CommonGraph executor vmaps
directly over the value/frontier state (and over stacked per-snapshot Δ
blocks).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.edgeset import EdgeBlock, EdgeView
from repro.graph.semiring import Semiring

INT_MAX = jnp.iinfo(jnp.int32).max
NO_PARENT = jnp.int32(-1)

Blocks = tuple[EdgeBlock, ...]


class FixpointResult(NamedTuple):
    """Final state of a fixpoint run plus its iteration/work accounting."""

    values: jnp.ndarray      # float32 [num_nodes]
    parent: jnp.ndarray      # int32  [num_nodes], -1 = none/source
    iterations: jnp.ndarray  # int32 scalar — sweeps executed
    edge_work: jnp.ndarray   # float32 scalar — frontier-masked edge relaxations
    # int32 scalar (per-lane when batched): |instability seed set| from the
    # stability analysis (graph/stability.py), None for from-scratch runs
    # where no Δ seeding happened. Identical under both seed modes.
    unstable: jnp.ndarray | None = None


class QueryState(NamedTuple):
    """A converged query state detached from its run statistics.

    The cross-launch unit of reuse: a ``(values, parent)`` pair extracted
    from a :class:`FixpointResult` can be cached (SnapshotStore's anchor
    family), re-seeded into a later incremental launch, or broadcast into
    batched lanes via :func:`gather_lane_states`. Values are a pure function
    of ``(edge set, semiring, source)`` — the monotone rounded fixpoint is
    unique, so a state reached by warm hops equals the from-scratch one
    bit-for-bit. Parents are dependence-valid but tie-break by construction
    path (only the deletion-trimming baseline consumes them).

    Cache-lifecycle hooks: :attr:`nbytes` is what the SnapshotStore LRU
    charges a cached state against its byte budget, and pin/release of a
    cached state is managed at the store layer (``SnapshotStore.pin`` /
    ``unpin`` / ``release(("AS",))``) — the state itself stays an immutable
    value, so pinning can never change what a launch computes.
    """

    values: jnp.ndarray      # float32 [num_nodes]
    parent: jnp.ndarray      # int32  [num_nodes]

    @property
    def nbytes(self) -> int:
        """Device footprint the store's LRU accounts for this state."""
        return sum(int(a.size) * a.dtype.itemsize for a in self)


def extract_state(res: FixpointResult) -> QueryState:
    """Detach the reusable (values, parent) state from a fixpoint result."""
    return QueryState(res.values, res.parent)


def host_sync(x):
    """Block until ``x`` (any array/pytree leaf holder) is computed on
    device, returning it — THE sanctioned host-sync point.

    Wall-clock numbers in run records are only honest if the device work
    they bracket has finished, but a stray ``block_until_ready`` inside a
    jitted function fails at trace time (and near the hot path it forces a
    host round-trip per sweep). graphlint rule G004 therefore bans bare
    syncs outside ``benchmarks/``; drivers and executors time through this
    helper instead, keeping every legal sync greppable from one name.
    """
    for leaf in jax.tree_util.tree_leaves(x):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return x


def init_values(num_nodes: int, semiring: Semiring, source: int) -> jnp.ndarray:
    """Fresh value vector: identity everywhere, source_value at source."""
    values = jnp.full((num_nodes,), semiring.identity, dtype=jnp.float32)
    return values.at[source].set(semiring.source_value)


def _segment_reduce(sr: Semiring, data, segment_ids, num_segments):
    if sr.is_min:
        return jax.ops.segment_min(data, segment_ids, num_segments)
    return jax.ops.segment_max(data, segment_ids, num_segments)


def _out_degree(num_nodes: int, blocks: Blocks) -> jnp.ndarray:
    """Real out-degree of every vertex over ``blocks`` (int32 [num_nodes]).

    Padding slots (``dst == num_nodes``) count for nothing. A sweep's edge
    work is this vector summed over the frontier, so the fixpoint computes
    it once per launch, outside its sweep loop; under ``vmap`` the shared
    blocks' part does not depend on the lane and stays unbatched.
    """
    deg = jnp.zeros((num_nodes,), jnp.int32)
    for src, dst, _ in blocks:
        deg = deg + jax.ops.segment_sum((dst < num_nodes).astype(jnp.int32),
                                        src, num_nodes)
    return deg


def _block_sweep(semiring: Semiring, num_nodes: int, masked, src, dst, w,
                 track_parents: bool = True):
    """One block's (best, winner_src) from the frontier-masked values.

    ``masked`` is ``values`` with every vertex outside the frontier set to
    the identity, so the sweep gathers once per edge slot: an inactive
    source gives ``combine(identity, w) == identity``, the candidate the
    two-gather form (``frontier[src]`` and ``values[src]``) gave it.

    ``track_parents=False`` (CommonGraph mode): dependence parents exist
    solely so KickStarter can trim on *deletions*. Deletion-free schedules
    (Direct-Hop / TG work-sharing) never trim, so the winner-src segment
    reduce — half the per-sweep segment ops — is skipped entirely. This is
    the paper's "deletions are what make streaming expensive" claim showing
    up inside the engine itself.
    """
    ident = jnp.float32(semiring.identity)
    # pad edges read masked[PAD_SRC]; their dst is the sentinel segment
    cand = semiring.combine(masked[src], w)
    blk_best = _segment_reduce(semiring, cand, dst, num_nodes + 1)[:num_nodes]
    if not track_parents:
        return blk_best, None
    # smallest src achieving this block's best (merged across blocks by
    # caller). Only improved vertices keep a winner, and their best is not
    # the identity, so no inactive edge (candidate: the identity) can tie.
    best_pad = jnp.concatenate([blk_best, jnp.float32([ident])])
    parent_cand = jnp.where(cand == best_pad[dst], src, INT_MAX)
    blk_winner = jax.ops.segment_min(parent_cand, dst, num_nodes + 1)[:num_nodes]
    return blk_best, blk_winner


def relax_sweep(
    semiring: Semiring,
    num_nodes: int,
    values: jnp.ndarray,
    parent: jnp.ndarray,
    frontier: jnp.ndarray,
    blocks: Blocks,
    gated: bool = False,
    track_parents: bool = True,
    out_deg: jnp.ndarray | None = None,
):
    """One frontier-masked relaxation sweep over all blocks.

    Masks the values by the frontier once (V elements), then gathers each
    block's masked values once per edge slot (:func:`_block_sweep`);
    exact because the identity is absorbing under ``combine``. ``work``
    is ``out_deg`` (default: :func:`_out_degree` of ``blocks``) summed over
    the frontier: the real edges leaving frontier vertices.

    ``gated`` (beyond-paper optimization): a block whose sources contain
    no frontier vertex is skipped entirely via lax.cond — the TPU-dense
    analogue of the CPU papers' per-vertex worklists at edge-block
    granularity. Exactness is unaffected (skipped blocks can only produce
    identity candidates).

    Returns (values, parent, improved, work).
    """
    ident = jnp.float32(semiring.identity)
    if out_deg is None:
        out_deg = _out_degree(num_nodes, blocks)
    masked = jnp.where(frontier, values, ident)
    best = jnp.full((num_nodes,), ident)
    bests = []
    for src, dst, w in blocks:
        if gated:
            none_winner = (jnp.full((num_nodes,), INT_MAX, dtype=jnp.int32)
                           if track_parents else None)
            blk_best, blk_winner = jax.lax.cond(
                jnp.any(frontier[src]),
                lambda s=src, d=dst, ww=w: _block_sweep(
                    semiring, num_nodes, masked, s, d, ww, track_parents),
                lambda: (jnp.full((num_nodes,), ident), none_winner),
            )
        else:
            blk_best, blk_winner = _block_sweep(
                semiring, num_nodes, masked, src, dst, w, track_parents)
        best = semiring.better(best, blk_best)
        bests.append((blk_best, blk_winner))
    work = jnp.sum(jnp.where(frontier, out_deg, 0)).astype(jnp.float32)

    improved = semiring.strictly_better(best, values)
    new_values = semiring.better(values, best)

    if not track_parents:
        return new_values, parent, improved, work

    # Dependence parent: the smallest src among edges achieving the global
    # best (per-block winners merged; only blocks matching the global best
    # contribute, which preserves the ungated tie-break exactly).
    winner = jnp.full((num_nodes,), INT_MAX, dtype=jnp.int32)
    for blk_best, blk_winner in bests:
        winner = jnp.where(blk_best == best,
                           jnp.minimum(winner, blk_winner), winner)
    new_parent = jnp.where(improved, winner, parent)
    return new_values, new_parent, improved, work


def relax_sweep_fused(
    semiring: Semiring,
    num_nodes: int,
    values: jnp.ndarray,
    parent: jnp.ndarray,
    frontier: jnp.ndarray,
    blocks: Blocks,
    k: int = 1,
    allowed: jnp.ndarray | None = None,
    gated: bool = False,
    track_parents: bool = True,
    use_pallas: bool = False,
    interpret: bool | None = None,
    out_deg: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Up to ``k`` frontier-masked sweeps as one fused chunk.

    The fused unit of execution ``_fixpoint`` consumes: a chunk runs sweeps
    until the frontier empties or ``min(k, allowed)`` is reached (``allowed``
    is a traced int32 cap, default ``k`` — the fixpoint driver uses it to
    respect ``max_iters`` exactly), and convergence checks inside the chunk
    never surface to the host. Two implementations, bit-identical by the
    differential harness (tests/test_kernels_diff.py):

    * reference (default): an inner ``lax.while_loop`` over
      :func:`relax_sweep` — the portable path and the engine's CPU default;
    * ``use_pallas=True``: the fused pallas kernel
      (``kernels/edge_relax_multi``), which keeps values/frontier
      VMEM-resident across all sweeps with an on-chip early exit. It runs
      in the Pallas interpreter on the CPU backend (``interpret=None``) and
      does not compile for TPU v5e yet, so ``_fixpoint`` never selects it.

    ``out_deg`` is the blocks' :func:`_out_degree`, computed here once per
    chunk when the caller has not hoisted it already.

    graphlint rule G010 grants this call to ``graph.stability`` seeding and
    the engine's ``_fixpoint`` only — everything else reaches fused sweeps
    through the launch stack's ``fused_k`` option.

    Returns ``(values, parent, frontier, sweeps, work)``.
    """
    if allowed is None:
        allowed = jnp.int32(k)
    if use_pallas:
        from repro.kernels import relax_multi
        from repro.kernels.edge_relax.edge_relax import KERNEL_OP_FOR
        src = jnp.concatenate([b[0] for b in blocks])
        dst = jnp.concatenate([b[1] for b in blocks])
        w = jnp.concatenate([b[2] for b in blocks])
        return relax_multi(values, parent, frontier, src, dst, w, allowed,
                           op=KERNEL_OP_FOR[semiring.name],
                           num_nodes=num_nodes, k=k,
                           track_parents=track_parents, interpret=interpret)
    if out_deg is None:
        out_deg = _out_degree(num_nodes, blocks)

    def cond(state):
        _, _, frontier, s, _ = state
        return jnp.logical_and(s < allowed, jnp.any(frontier))

    def body(state):
        values, parent, frontier, s, work = state
        values, parent, improved, dw = relax_sweep(
            semiring, num_nodes, values, parent, frontier, blocks,
            gated=gated, track_parents=track_parents, out_deg=out_deg)
        return values, parent, improved, s + 1, work + dw

    init = (values, parent, frontier, jnp.int32(0), jnp.float32(0))
    return jax.lax.while_loop(cond, body, init)


def _fixpoint(semiring: Semiring, num_nodes: int, max_iters: int,
              values, parent, frontier, blocks: Blocks,
              gated: bool = False, track_parents: bool = True,
              fused_k: int = 1) -> FixpointResult:
    def cond(state):
        _, _, frontier, it, _ = state
        return jnp.logical_and(it < max_iters, jnp.any(frontier))

    # hoisted out of the sweep loop: one out-degree scatter per block per
    # launch instead of a second per-slot gather in every sweep
    out_deg = _out_degree(num_nodes, blocks)

    if fused_k > 1:
        # Consume fused chunks: each outer step advances up to fused_k
        # sweeps via relax_sweep_fused, so the host-visible convergence
        # check runs once per chunk instead of once per sweep. The sweep
        # sequence (and therefore values/parent/iterations/edge_work) is
        # bit-identical to the unfused loop: the chunk's dynamic cap
        # min(fused_k, max_iters - it) never overruns max_iters, and the
        # chunk stops early the moment the frontier empties.
        def chunk_body(state):
            values, parent, frontier, it, work = state
            cap = jnp.minimum(jnp.int32(fused_k), max_iters - it)
            values, parent, frontier, s, dw = relax_sweep_fused(
                semiring, num_nodes, values, parent, frontier, blocks,
                k=fused_k, allowed=cap, gated=gated,
                track_parents=track_parents, out_deg=out_deg)
            return values, parent, frontier, it + s, work + dw

        init = (values, parent, frontier, jnp.int32(0), jnp.float32(0))
        values, parent, _, it, work = jax.lax.while_loop(cond, chunk_body,
                                                         init)
        return FixpointResult(values, parent, it, work)

    def body(state):
        values, parent, frontier, it, work = state
        values, parent, improved, dw = relax_sweep(
            semiring, num_nodes, values, parent, frontier, blocks, gated=gated,
            track_parents=track_parents, out_deg=out_deg)
        return values, parent, improved, it + 1, work + dw

    init = (values, parent, frontier, jnp.int32(0), jnp.float32(0))
    values, parent, _, it, work = jax.lax.while_loop(cond, body, init)
    return FixpointResult(values, parent, it, work)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 7, 8, 9))
def _fixpoint_jit(semiring, num_nodes, max_iters, values, parent, frontier,
                  blocks, gated=False, track_parents=True, fused_k=1):
    return _fixpoint(semiring, num_nodes, max_iters, values, parent, frontier,
                     blocks, gated, track_parents, fused_k)


def run_to_fixpoint(
    view: EdgeView,
    semiring: Semiring,
    source: int,
    max_iters: int = 10_000,
    values: jnp.ndarray | None = None,
    parent: jnp.ndarray | None = None,
    frontier: jnp.ndarray | None = None,
    gated: bool = False,
    track_parents: bool = True,
    fused_k: int = 1,
) -> FixpointResult:
    """Run the query to fixpoint on ``view`` (from scratch or a warm state).

    ``fused_k`` > 1 makes the fixpoint consume fused chunks of up to that
    many sweeps per convergence check (:func:`relax_sweep_fused`) — a pure
    launch-shape knob, bit-identical results at any value.
    """
    n = view.num_nodes
    fresh = values is None
    if fresh:
        values = init_values(n, semiring, source)
    if parent is None:
        parent = jnp.full((n,), NO_PARENT, dtype=jnp.int32)
    if frontier is None:
        # Fresh start: only the source can seed improvements. Warm start with
        # an unknown perturbation: every vertex may need to re-propagate.
        frontier = (jnp.zeros((n,), bool).at[source].set(True) if fresh
                    else jnp.ones((n,), bool))
    return _fixpoint_jit(semiring, n, max_iters, values, parent, frontier,
                         tuple(view.blocks), gated, track_parents, fused_k)


def incremental_additions(
    view: EdgeView,
    added: EdgeView | EdgeBlock,
    semiring: Semiring,
    values: jnp.ndarray,
    parent: jnp.ndarray,
    max_iters: int = 10_000,
    gated: bool = False,
    track_parents: bool = True,
    seed: str = "instability",
    fused_k: int = 1,
) -> FixpointResult:
    """Addition-only incremental update (the cheap KickStarter direction).

    ``view`` must already include the added blocks; ``added`` is just the new
    edges. Seeds the frontier from the stable-vertex analysis
    (graph/stability.py): the Δ edges are relaxed once against the anchor
    state and only the vertices they strictly improved — the instability
    set — enter the fixpoint frontier. ``seed="delta"`` keeps the full-Δ
    baseline seeding (identical values/parents, more seed work; see
    ``stability.seed_state``). Monotonicity guarantees the exact
    from-scratch fixpoint is reached either way.
    """
    from repro.graph.stability import seed_state
    n = view.num_nodes
    add_blocks = (added,) if isinstance(added, EdgeBlock) else tuple(added.blocks)
    seeded = seed_state(semiring, n, values, parent, add_blocks,
                        mode=seed, track_parents=track_parents)
    res = _fixpoint_jit(semiring, n, max_iters, seeded.values, seeded.parent,
                        seeded.frontier, tuple(view.blocks), gated,
                        track_parents, fused_k)
    return FixpointResult(res.values, res.parent, res.iterations + 1,
                          res.edge_work + seeded.seed_work, seeded.unstable)


# ---------------------------------------------------------------------------
# Batched (snapshot-axis) execution: the paper's "breaks the sequential
# dependency" parallelism, realized as one extra tensor axis. Shared blocks
# broadcast; per-snapshot Δ blocks are stacked on axis 0.
# ---------------------------------------------------------------------------

def gather_lane_states(values: jnp.ndarray, parent: jnp.ndarray,
                       lane_to_parent) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Gather per-lane warm-start states for a batched launch.

    ``values``/``parent`` are the previous level's stacked states
    ``[P, N]``; ``lane_to_parent[l]`` names the parent lane whose state
    seeds lane ``l``. Both batched executors route through here: the
    level-synchronous TG executor gathers each sibling's parent state
    (a permutation-with-repeats of the previous level), and the
    sliding-window executor broadcasts the single anchor state to every
    window lane (``lane_to_parent == zeros``). One gather instead of a
    host-side stack keeps the states on-device (and sharded, if they are).
    """
    idx = jnp.asarray(np.asarray(lane_to_parent, dtype=np.int32))
    return values[idx], parent[idx]

def batched_incremental(semiring, num_nodes, max_iters,
                        values, parent, shared_blocks, delta_blocks,
                        track_parents=True, gated=False, seed_blocks=None,
                        lane_valid=None, seed="instability", fused_k=1):
    """vmapped incremental additions (unjitted; launch/dryrun jits with shardings).

    values/parent: [S, N]; shared_blocks: tuple of EdgeBlock (broadcast);
    delta_blocks: tuple of EdgeBlock with leading S axis (stacked).

    ``seed_blocks`` (stacked like delta_blocks, default: all of them): the
    blocks the frontier is seeded from. The level-synchronous TG executor
    carries each lane's *cumulative* Δ from the apex in delta_blocks but
    seeds only from the lane's final parent→child hop, matching the
    sequential executor's per-hop seeding (and its edge-work accounting)
    exactly.

    ``seed`` selects the per-lane seeding mode (graph/stability.py):
    ``"instability"`` masks each lane's seed sweep to its reached vertices
    — the stable-vertex analysis — and ``"delta"`` is the full-Δ baseline.
    Bit-identical results either way; the lane's ``unstable`` count and
    ``edge_work`` are what differ.

    ``lane_valid`` ([S] bool, default: all valid): marks padding lanes the
    executors appended to reach a ``lane_bucket`` (pow2, mesh-divisible)
    lane count. A masked lane carries an all-sentinel Δ and a copied anchor
    state, so its values stay inert by construction; the mask additionally
    zeroes its ``iterations``/``edge_work``/``unstable`` so work and
    stability accounting stay bit-equal to the sequential executors
    regardless of padding.
    """
    from repro.graph.stability import seed_state
    seeds = delta_blocks if seed_blocks is None else seed_blocks

    def one(values, parent, delta_blocks, seed_blocks):
        seeded = seed_state(semiring, num_nodes, values, parent, seed_blocks,
                            mode=seed, track_parents=track_parents)
        res = _fixpoint(semiring, num_nodes, max_iters, seeded.values,
                        seeded.parent, seeded.frontier,
                        shared_blocks + delta_blocks, gated=gated,
                        track_parents=track_parents, fused_k=fused_k)
        return FixpointResult(res.values, res.parent, res.iterations + 1,
                              res.edge_work + seeded.seed_work,
                              seeded.unstable)

    res = jax.vmap(one, in_axes=(0, 0, 0, 0))(values, parent,
                                              delta_blocks, seeds)
    if lane_valid is None:
        return res
    return FixpointResult(
        res.values, res.parent,
        jnp.where(lane_valid, res.iterations, 0),
        jnp.where(lane_valid, res.edge_work, jnp.float32(0)),
        jnp.where(lane_valid, res.unstable, 0))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 7, 8, 11, 12))
def _batched_incremental_jit(semiring, num_nodes, max_iters,
                             values, parent, shared_blocks, delta_blocks,
                             track_parents=True, gated=False,
                             seed_blocks=None, lane_valid=None,
                             seed="instability", fused_k=1):
    return batched_incremental(semiring, num_nodes, max_iters,
                               values, parent, shared_blocks, delta_blocks,
                               track_parents, gated, seed_blocks, lane_valid,
                               seed, fused_k)


def incremental_additions_batched(
    num_nodes: int,
    semiring: Semiring,
    values: jnp.ndarray,          # [S, N]
    parent: jnp.ndarray,          # [S, N]
    shared_blocks: Blocks,        # broadcast to all snapshots
    delta_blocks: Blocks,         # each with leading [S] axis
    max_iters: int = 10_000,
    track_parents: bool = True,
    gated: bool = False,
    seed_blocks: Blocks | None = None,
    lane_valid: jnp.ndarray | None = None,  # [S] bool; False = padding lane
    seed: str = "instability",
    fused_k: int = 1,
) -> FixpointResult:
    """Batched addition-only updates, one lane per Δ (see batched_incremental).

    Bit-identical per lane to :func:`incremental_additions`; ``fused_k``
    sets the sweeps-per-dispatch chunk size, a pure launch-shape knob.
    """
    return _batched_incremental_jit(semiring, num_nodes, max_iters,
                                    values, parent, tuple(shared_blocks),
                                    tuple(delta_blocks), track_parents, gated,
                                    None if seed_blocks is None
                                    else tuple(seed_blocks), lane_valid, seed,
                                    fused_k)
