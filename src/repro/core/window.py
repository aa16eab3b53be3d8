"""Sliding-window executors: many window queries as one batched launch.

The dominant query pattern over an evolving sequence is not "every
snapshot" but a *window* that slides: answer the query on ``[i, i+w)``,
then on ``[i+1, i+w+1)``, and so on (delta-based historical queries à la
Koloniari et al.; the streaming-system surveys make the same point). The
naive slide re-runs the query per window. CommonGraph makes every window
an *addition-only* hop from a shared anchor:

* Sliding is NOT deletion-free between consecutive windows — ``T(i,j) ⊄
  T(i+1,j+1)`` in general. The sound warm start is any common
  SUPER-window's apex: for windows spanning ``[lo..hi]`` the tightest is
  ``T(lo, hi)`` (every window's common graph contains it), which
  ``window_anchor`` picks by default.
* With one anchor fixpoint in hand, each window apex is reached by
  streaming ``slide_block(window, anchor)`` — pure additions. The hops are
  mutually independent, so the batched executor stacks them as lanes of a
  single ``incremental_additions_batched`` launch
  (``SnapshotStore.slide_stack``), exactly the level-batching machinery of
  ``core/trigrid.py`` with windows instead of plan levels.

Executor contract (same as core/trigrid.py, enforced by
tests/test_window.py):

* **Bit-identical results.** ``run_window_slide_batched`` returns values
  (and parents, when tracked) bit-identical to the sequential
  ``run_window_slide`` for the same windows/anchor/options: every lane
  converges over exactly the edge set the sequential hop uses (anchor
  blocks + that window's slide Δ), and the monotone fixpoint is
  order-free. Both match a from-scratch fixpoint on each window's common
  graph up to float tolerance.
* **Shape-bucketing invariant.** The stacked slide Δ has shape
  ``(pow2 lane bucket, pow2 width bucket)``: the window-lane axis pads to
  ``lane_bucket(num_windows, data_extent)`` with trailing masked lanes
  (all-sentinel Δ, anchor-state copy, ``lane_valid=False``, zero
  work/iterations), so jit traces are keyed on buckets alone and any
  window count shards over a ``data`` mesh — the replicated fallback (and
  its UserWarning) no longer exists.
* **Degenerate cases.** A single window equal to the anchor is legal: its
  Δ is empty, the seed sweep finds no improvements, and the anchor state
  is returned unchanged. Likewise ``width == num_snapshots`` yields one
  window (the global CG query itself).
* **Work accounting.** Padding never counts toward ``edge_work``; batched
  and sequential slides report equal per-window totals.

Streaming campaigns (``WindowStream`` / ``run_window_stream_batched``) layer
cross-launch anchor reuse on top: an advancing window sequence is cut into
campaigns of ``campaign_width`` windows, each campaign runs as one batched
slide launch anchored at ``(campaign_lo, stream_hi)``, and the anchor STATE
is maintained incrementally — campaign k+1's anchor window is nested in
campaign k's (its common graph is a pure-addition extension), so k's
converged state seeds an ``incremental_additions`` hop instead of a
from-scratch rebuild. States live in ``SnapshotStore``'s LRU-cached "AS"
family, so back-to-back campaigns (and repeat stream calls) hit memory, not
recompute; eviction mid-stream costs exactly one rebuild and never changes
results. The stream contract, enforced by tests/test_window_stream.py:

* **Bit-identical to cold campaigns.** ``run_window_stream_batched`` window
  values equal ``run_window_slide_batched`` run cold per campaign (same
  windows, same anchor) bit-for-bit — the monotone rounded fixpoint of a
  window's common graph is unique, so how the anchor state was reached
  (from-scratch vs incremental hops) is unobservable in values.
* **Strictly fewer rebuilds.** A K-campaign stream performs 1 anchor
  rebuild + K−1 incremental anchor hops (plus one rebuild per mid-stream
  eviction) vs the cold path's K rebuilds.

Two layers complete the subsystem (docs/STREAMING.md is the full guide):

* **Campaign planning** (``optimal_campaigns`` / ``CampaignPlan``): the
  campaign partition itself is chosen by Δ-volume — a suffix DP over cut
  points pricing slide hops, anchor hops and the pow2 masked-lane padding
  from the same ``hop_added_edges`` atom as the TG plan DP.
  ``campaign_width="auto"`` routes the executor through it;
  ``campaign_volume`` prices any partition under the identical model, so
  auto is provably never worse than any fixed width ≤ ``lane_budget``.
* **Anchor chains** (``AnchorChain`` / ``select_chain``): overlapping
  streams share one chain of nested anchor states. Links are pinned in the
  store while any registered stream is still behind them, so a lagging
  stream's next hop source cannot be evicted; values are unaffected either
  way (unique monotone fixpoint) — sharing only converts rebuilds into
  hops/hits.
"""

from __future__ import annotations

import dataclasses
import itertools
import time

import jax.numpy as jnp
import numpy as np

from repro.core.kickstarter import StreamStats
from repro.core.snapshots import SnapshotStore, anchor_tag, tightest_cover
from repro.core.trigrid import (
    _anchor_base,
    _anchor_view,
    _shard_snapshot_axis,
    _sweep_counts,
    hop_added_edges,
)
from repro.graph.edgeset import lane_bucket
from repro.graph.engine import (
    QueryState,
    extract_state,
    gather_lane_states,
    host_sync,
    incremental_additions,
    incremental_additions_batched,
)
from repro.graph.semiring import Semiring
from repro.graph.stability import stable_fraction_milli
from repro.runtime.spans import span

Window = tuple[int, int]


def slide_windows(num_snapshots: int, width: int, step: int = 1,
                  start: int = 0) -> list[Window]:
    """Window plan construction: all width-``width`` windows sliding by ``step``.

    Windows are inclusive snapshot-index pairs ``(i, i + width - 1)``; the
    last one ends at the final snapshot. Degenerate cases are explicit: a
    width covering the whole (remaining) sequence yields exactly one
    window.
    """
    if not 1 <= width <= num_snapshots - start:
        raise ValueError(
            f"window width {width} not in [1, {num_snapshots - start}] "
            f"(num_snapshots={num_snapshots}, start={start})")
    if step < 1:
        raise ValueError(f"step must be >= 1, got {step}")
    return [(i, i + width - 1)
            for i in range(start, num_snapshots - width + 1, step)]


def window_anchor(windows: list[Window]) -> Window:
    """Tightest common super-window: the span of all windows.

    Every window's common graph contains the span's (nested windows ⇒
    nested CGs), so the span apex warm-starts every slide hop with the
    largest possible shared state — strictly less Δ volume than anchoring
    at the global CG when the windows don't cover the whole sequence.
    """
    if not windows:
        raise ValueError("need at least one window")
    return min(i for i, _ in windows), max(j for _, j in windows)


@dataclasses.dataclass
class WindowSlideRun:
    """Result record of one window slide: per-window values plus the
    shared-anchor fixpoint stats, per-hop stats and Δ-volume/lane
    accounting the benchmarks compare executors by."""

    results: dict[Window, jnp.ndarray]  # window -> values
    anchor: Window
    base_stats: StreamStats             # the shared anchor fixpoint
    hop_stats: list[StreamStats]        # per-window (seq) or 1 launch (batched)
    wall_s: float
    added_edges: int                    # total slide-Δ volume streamed
    # (valid lanes, lane_bucket) of the batched launch; empty when sequential
    lane_layout: "list[tuple[int, int]]" = dataclasses.field(
        default_factory=list)
    # measured stable fraction (‰) over all window hops: the share of
    # vertex-lanes the stability analysis kept out of the seed frontier
    # (graph/stability.py; padding lanes excluded)
    stable_milli: int = 0


def _slide_added_edges(store: SnapshotStore, windows: list[Window],
                       anchor: Window) -> int:
    """Total slide-Δ volume of hopping every window off ``anchor``.

    Each window apex is one grid hop T(anchor) → T(window), so the volume
    is a sum of ``hop_added_edges`` atoms — the same cost atom the TG plan
    DP and the campaign planner (``optimal_campaigns``) optimize over.
    """
    return sum(hop_added_edges(store, anchor, w) for w in windows)


def _resolve(store: SnapshotStore, width: int | None, windows, step, start,
             anchor):
    if windows is None:
        if width is None:
            raise ValueError("pass either width= or windows=")
        windows = slide_windows(store.seq.num_snapshots, width, step=step,
                                start=start)
    windows = [tuple(w) for w in windows]
    if anchor is None:
        anchor = window_anchor(windows)
    return windows, tuple(anchor)


def run_window_slide(
    store: SnapshotStore,
    semiring: Semiring,
    source: int,
    width: int | None = None,
    *,
    windows: "list[Window] | None" = None,
    step: int = 1,
    start: int = 0,
    anchor: Window | None = None,
    max_iters: int = 10_000,
    gated: bool = False,
    cg_split: int = 1,
    track_parents: bool = False,
    seed: str = "instability",
    fused_k: int = 1,
) -> WindowSlideRun:
    """Sequential window slide: one anchor fixpoint, then per-window hops.

    The baseline the batched executor is measured (and bit-compared)
    against: each window re-executes ``incremental_additions`` from the
    anchor state with that window's slide Δ, seeded per the stable-vertex
    analysis (``seed="delta"`` restores full-Δ seeding; values identical).
    ``fused_k`` threads to the engine's fused-chunk launch option
    (bit-identical results at any value).
    """
    t_all = time.perf_counter()
    windows, anchor = _resolve(store, width, windows, step, start, anchor)
    anchor_view, base, base_stats = _anchor_base(
        store, anchor, semiring, source, max_iters, gated, cg_split,
        track_parents, fused_k)

    results: dict[Window, jnp.ndarray] = {}
    hop_stats: list[StreamStats] = []
    unstable_counts: list[int] = []
    for wnd in windows:
        t0 = time.perf_counter()
        delta = store.slide_block(wnd, anchor)
        view = anchor_view.extended(delta)       # shared immutable blocks
        res = incremental_additions(view, delta, semiring, base.values,
                                    base.parent, max_iters, gated=gated,
                                    track_parents=track_parents, seed=seed,
                                    fused_k=fused_k)
        host_sync(res.values)
        hop_stats.append(StreamStats(time.perf_counter() - t0,
                                     float(res.edge_work),
                                     int(res.iterations)))
        unstable_counts.append(int(res.unstable))
        results[wnd] = res.values
    return WindowSlideRun(results, anchor, base_stats, hop_stats,
                          time.perf_counter() - t_all,
                          _slide_added_edges(store, windows, anchor),
                          stable_milli=stable_fraction_milli(
                              unstable_counts, store.num_nodes))


def run_window_slide_batched(
    store: SnapshotStore,
    semiring: Semiring,
    source: int,
    width: int | None = None,
    *,
    windows: "list[Window] | None" = None,
    step: int = 1,
    start: int = 0,
    anchor: Window | None = None,
    max_iters: int = 10_000,
    gated: bool = False,
    cg_split: int = 1,
    track_parents: bool = False,
    mesh=None,
    seed: str = "instability",
    fused_k: int = 1,
) -> WindowSlideRun:
    """Batched window slide: every slide hop as a lane of ONE stacked launch.

    The anchor state broadcasts to all window lanes
    (``gather_lane_states`` with an all-zeros lane map), the per-window
    slide Δs stack shape-bucketed (``SnapshotStore.slide_stack``, lane axis
    padded to ``lane_bucket(num_windows, data_extent)`` with masked inert
    lanes), and one ``incremental_additions_batched`` call re-converges
    every window. On a mesh the bucketed window-lane axis ALWAYS shards
    over ``data`` exactly like the TG executor's snapshot axis
    (``launch/evolve.py --shard --window-batch``).
    """
    t_all = time.perf_counter()
    windows, anchor = _resolve(store, width, windows, step, start, anchor)
    anchor_view, base, base_stats = _anchor_base(
        store, anchor, semiring, source, max_iters, gated, cg_split,
        track_parents, fused_k)

    res, bucket, stats = _slide_launch(store, semiring, anchor_view,
                                       extract_state(base), windows, anchor,
                                       max_iters=max_iters, gated=gated,
                                       track_parents=track_parents, mesh=mesh,
                                       seed=seed, fused_k=fused_k)
    hop_stats = [stats]
    results = {wnd: res.values[lane] for lane, wnd in enumerate(windows)}
    return WindowSlideRun(results, anchor, base_stats, hop_stats,
                          time.perf_counter() - t_all,
                          _slide_added_edges(store, windows, anchor),
                          [(len(windows), bucket)],
                          stable_milli=stable_fraction_milli(
                              np.asarray(res.unstable)[:len(windows)],
                              store.num_nodes))


def _slide_launch(store: SnapshotStore, semiring: Semiring, anchor_view,
                  state: "QueryState | list[QueryState]",
                  windows: "list[Window]", anchor: Window,
                  *, max_iters: int, gated: bool, track_parents: bool, mesh,
                  lane_map: "list[int] | None" = None,
                  seed: str = "instability", fused_k: int = 1):
    """ONE stacked launch re-converging every window from anchor state(s).

    The shared campaign body of ``run_window_slide_batched``, the streaming
    scheduler and the query service's admission packer. ``state`` is either
    a single :class:`QueryState` broadcast to every window lane (the
    default, ``lane_map=None``), or — when ``lane_map`` is given — a list
    of states with ``lane_map[k]`` naming the state that seeds window lane
    ``k``: how ``core/service.py`` packs same-options queries for DIFFERENT
    (semiring-compatible) sources into one launch, each lane warm-starting
    from its own query's anchor state. Masked padding lanes ride along as
    inert copies of the first mapped state — their Δ is all-sentinel and
    ``lane_valid`` zeroes them out of the work accounting. The per-window
    slide Δs stack shape-bucketed, and one
    ``incremental_additions_batched`` call runs the lanes (sharded over
    ``data`` when a mesh is given).

    Returns ``(FixpointResult, bucket, StreamStats)``: the stats hold the
    launch's summed edge work and slowest lane's sweeps, copied to the
    host once here, inside the launch's ``cg.launch`` span.
    """
    if lane_map is not None and len(lane_map) != len(windows):
        raise ValueError(f"lane_map names {len(lane_map)} lanes for "
                         f"{len(windows)} windows")
    t0 = time.perf_counter()
    with span("cg.launch") as sp:
        data_extent = mesh.shape["data"] if mesh is not None else 1
        bucket = lane_bucket(len(windows), data_extent)
        stacked = store.slide_stack(windows, anchor, num_lanes=bucket)
        if lane_map is None:
            states, lane_map = [state], [0] * len(windows)
        else:
            states = list(state)
        lane_map = list(lane_map) + [lane_map[0]] * (bucket - len(windows))
        values, parent = gather_lane_states(
            jnp.stack([s.values for s in states]),
            jnp.stack([s.parent for s in states]), lane_map)
        lane_valid = jnp.arange(bucket) < len(windows)
        delta_blocks = (stacked,)
        values, parent, delta_blocks, lane_valid = _shard_snapshot_axis(
            mesh, values, parent, delta_blocks, lane_valid)
        res = incremental_additions_batched(
            store.num_nodes, semiring, values, parent,
            shared_blocks=tuple(anchor_view.blocks), delta_blocks=delta_blocks,
            max_iters=max_iters, track_parents=track_parents, gated=gated,
            seed_blocks=(delta_blocks[-1],), lane_valid=lane_valid, seed=seed,
            fused_k=fused_k)
        host_sync(res.values)
        stats = StreamStats(time.perf_counter() - t0,
                            float(jnp.sum(res.edge_work)),
                            int(jnp.max(res.iterations)))
        if sp.live:
            base = store.window_size(*anchor)
            added = sum(store.window_size(*w) - base for w in windows)
            sp.set(**_sweep_counts(stats, bucket,
                                   anchor_view.blocks + delta_blocks,
                                   bucket * base + added, delta_blocks, added))
    return res, bucket, stats


# ---------------------------------------------------------------------------
# Streaming slide campaigns: cross-launch incremental anchor maintenance.
# ---------------------------------------------------------------------------


def _validate_advancing(windows: "list[Window]", tail: Window | None = None):
    prev = tail
    for wnd in windows:
        i, j = wnd
        if j < i:
            raise ValueError(f"window {wnd} is empty: need i <= j")
        if prev is not None and (i < prev[0] or j < prev[1]):
            raise ValueError(
                f"windows must advance: {wnd} steps backwards from {prev} "
                "(both endpoints must be nondecreasing)")
        prev = wnd


#: ``campaign_width`` sentinel: let ``optimal_campaigns`` choose the
#: partition by Δ-volume instead of cutting fixed-width chunks.
CAMPAIGN_AUTO = "auto"

_STREAM_COUNTER = itertools.count()


def _valid_campaign_width(width) -> bool:
    return width == CAMPAIGN_AUTO or (isinstance(width, int) and width >= 1)


@dataclasses.dataclass
class WindowStream:
    """An advancing window sequence consumed campaign-by-campaign.

    The streaming producer side of ``run_window_stream_batched``: windows
    arrive in slide order (both endpoints nondecreasing — enforced), are
    buffered here, and each executor call drains the pending buffer as
    campaigns of ``campaign_width`` windows (``"auto"`` = let
    ``optimal_campaigns`` pick the partition by Δ-volume). The stream
    object itself holds no query state — anchors live in the
    SnapshotStore's "AS" cache family, which is what lets a stream span
    many launches (and many stream objects) while anchor work stays
    incremental. ``name`` identifies the stream to an :class:`AnchorChain`
    when several overlapping streams share one (auto-generated unless
    given).

    ``feed`` attaches a live window source (``ingest.LiveWindowFeed``):
    the stream then blocks on the watermark instead of a precomputed
    list — every ``pending``/``take``/``take_next`` first polls the feed
    for windows born by new snapshot cuts, and every consumption reports
    progress back so the feed's compaction floor tracks the oldest
    snapshot an unconsumed window still needs.
    """

    campaign_width: "int | str"
    windows: "list[Window]" = dataclasses.field(default_factory=list)
    consumed: int = 0
    name: "str | None" = None
    feed: "object | None" = None

    def __post_init__(self):
        if not _valid_campaign_width(self.campaign_width):
            raise ValueError(
                f'campaign_width must be an int >= 1 or "auto", '
                f"got {self.campaign_width!r}")
        self.windows = [tuple(w) for w in self.windows]
        _validate_advancing(self.windows)
        if self.name is None:
            self.name = f"stream-{next(_STREAM_COUNTER)}"
        self._sync_feed()

    def _sync_feed(self) -> None:
        # Pull windows born from the live feed since the last poll. Duck-
        # typed (anything with poll()) so window.py never imports ingest.py.
        if self.feed is not None:
            born = self.feed.poll()
            if born:
                self.extend(born)

    def _report_feed(self) -> None:
        # Report consumption so the feed's compaction floor advances: the
        # oldest snapshot still needed is the first unconsumed window's lo.
        if self.feed is not None:
            rest = self.windows[self.consumed:]
            self.feed.advance_floor(rest[0][0] if rest else None)

    def extend(self, windows: "list[Window]") -> "WindowStream":
        """Append newly arrived windows (must keep the sequence advancing)."""
        windows = [tuple(w) for w in windows]
        _validate_advancing(windows,
                            tail=self.windows[-1] if self.windows else None)
        self.windows.extend(windows)
        return self

    def pending(self) -> "list[Window]":
        """Windows buffered but not yet consumed by the executor.

        With a live ``feed``, polls it first so freshly cut windows count.
        """
        self._sync_feed()
        return self.windows[self.consumed:]

    def take(self) -> "list[Window]":
        """Drain and return the pending windows (executor entry point)."""
        out = self.pending()
        self.consumed = len(self.windows)
        self._report_feed()
        return out

    def take_next(self, count: int) -> "list[Window]":
        """Consume and return up to ``count`` pending windows.

        The query service's bounded per-turn draw: one scheduler turn takes
        at most a campaign's worth of windows from each stream so no client
        monopolizes a turn (``take()`` drains everything — the
        stream-at-a-time executor's entry point). With a live ``feed`` this
        is the blocking-on-the-watermark call: it returns only windows
        whose newest snapshot has been cut, possibly none.
        """
        self._sync_feed()
        out = self.windows[self.consumed:self.consumed + count]
        self.consumed += len(out)
        self._report_feed()
        return out


def stream_campaigns(windows: "list[Window]",
                     campaign_width: int) -> "list[list[Window]]":
    """Cut an advancing window sequence into consecutive fixed-width campaigns.

    Campaigns are disjoint chunks of ``campaign_width`` windows (the last
    may be short); their SPANS overlap whenever consecutive windows do —
    which is exactly what the incremental anchor chain exploits. The
    ``"auto"`` sentinel is NOT resolved here (fixed-width chunking needs no
    store): ``run_window_stream_batched(campaign_width="auto")`` partitions
    via ``optimal_campaigns`` instead of this function.
    """
    if campaign_width == CAMPAIGN_AUTO:
        raise ValueError(
            'campaign_width="auto" needs a SnapshotStore to plan against — '
            "partition via optimal_campaigns(store, windows), which is what "
            'run_window_stream_batched(campaign_width="auto") does')
    if not _valid_campaign_width(campaign_width):
        raise ValueError(f'campaign_width must be an int >= 1 or "auto", '
                         f"got {campaign_width!r}")
    return [windows[k:k + campaign_width]
            for k in range(0, len(windows), campaign_width)]


# ---------------------------------------------------------------------------
# Campaign planner: Δ-volume DP over the campaign partition.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CampaignPlan:
    """A campaign partition of an advancing window sequence + modeled cost.

    The planner's unit of exchange: ``optimal_campaigns`` returns the
    Δ-volume-minimal plan, ``campaign_volume`` evaluates ANY partition
    (fixed-width chunkings included) under the same cost model, so plans
    are directly comparable. The model counts edges the launches actually
    process:

    * ``slide_edges`` — exact window-hop Δ volume: every window streams
      ``|T(window)| − |T(anchor)|`` addition edges off its campaign anchor.
    * ``anchor_edges`` — anchor-chain volume: the first anchor's
      from-scratch rebuild (``|T(anchor_0)|``) plus each later campaign's
      incremental hop (``|T(anchor_k)| − |T(anchor_{k−1})|``). The hops
      telescope, so this always equals ``|T(anchor_last)|`` — narrower
      last campaigns pay more here.
    * ``padding_edges`` — the pow2 masked-lane penalty: a campaign of L
      windows launches ``lane_bucket(L, data_extent)`` lanes, and each of
      the ``bucket − L`` masked lanes rides along at the campaign's widest
      slide Δ (the stacked buffer's lane width). This is device volume,
      not streamed edges — it is what makes width 5 more expensive than
      width 4 even when the exact Δ sums agree.

    ``stable_milli`` records the instability discount the model was priced
    under: the stable-vertex analysis (graph/stability.py) lets each seed
    sweep skip Δ edges leaving unreached vertices, so every
    ``hop_added_edges`` atom is scaled by ``(1000 − stable_milli) / 1000``
    before entering the slide/pad/anchor-hop terms. The default 0 prices
    raw Δ volume (no discount); a caller with a measured fraction from a
    prior run (e.g. the warm-up stream in ``launch/evolve.py``) passes it
    in so the plan prices the work the executors will actually do. The
    discount is applied at the ATOM level in both ``campaign_volume`` and
    the ``optimal_campaigns`` DP, so DP cost equals partition price and
    auto ≤ fixed-width holds for any ``stable_milli``.
    """

    campaigns: "list[list[Window]]"
    anchors: "list[Window]"              # per-campaign (lo_k, stream_hi)
    lane_budget: int
    data_extent: int
    slide_edges: int
    anchor_edges: int
    padding_edges: int
    # instability discount (‰ stable) the volumes above were priced under
    stable_milli: int = 0
    # measured-cost model (core/costmodel.SweepCostModel) the volumes were
    # priced under, or None for the raw discounted edge-count objective
    cost_model: object = None

    @property
    def widths(self) -> "list[int]":
        """Per-campaign window counts (the partition's shape)."""
        return [len(c) for c in self.campaigns]

    @property
    def total_edges(self) -> int:
        """The planner's objective: slide + anchor + masked-lane volume.

        With a ``cost_model`` the unit is integer nanoseconds of modeled
        launch time rather than discounted edge count — still an exact
        integer, so plan comparisons stay machine-independent.
        """
        return self.slide_edges + self.anchor_edges + self.padding_edges


def _instability_volume(edges: int, stable_milli: int) -> int:
    """One Δ-volume atom discounted by the modeled stable fraction (‰).

    The stability analysis keeps ``stable_milli``/1000 of vertex-lanes out
    of the seed frontier, so a hop's effective Δ volume shrinks to
    ``edges · (1000 − stable_milli) / 1000`` (floor division — integers
    keep the DP/partition-price equality exact). ``stable_milli=0`` is the
    identity, so undiscounted plans are bit-stable.
    """
    if not 0 <= stable_milli <= 1000:
        raise ValueError(f"stable_milli must be in [0, 1000], "
                         f"got {stable_milli!r}")
    return edges * (1000 - stable_milli) // 1000


def campaign_volume(store: SnapshotStore, campaigns: "list[list[Window]]",
                    *, data_extent: int = 1,
                    lane_budget: "int | None" = None,
                    stable_milli: int = 0,
                    cost_model=None) -> CampaignPlan:
    """Evaluate a campaign partition under the planner's Δ-volume model.

    Anchors each campaign exactly as ``run_window_stream_batched`` does —
    ``(campaign_lo, stream_hi)`` — and prices it per the
    :class:`CampaignPlan` field docs. Works for any partition of any
    advancing window sequence, which is what lets tests (and the planner
    itself) compare ``optimal_campaigns`` against every fixed-width
    chunking on equal terms. ``stable_milli`` applies the instability
    discount (:func:`_instability_volume`) to every hop atom — slide Δs,
    masked-lane padding and incremental anchor hops; the first anchor's
    from-scratch rebuild is NOT a Δ-seeded sweep and prices undiscounted.

    With a ``cost_model`` (core/costmodel.SweepCostModel, duck-typed) every
    hop atom prices via ``cost_model.hop_cost(edges)`` instead — the
    model's own ``stable_milli`` applies and this function's
    ``stable_milli`` argument is ignored — and the first anchor via
    ``cost_model.anchor_cost``; volumes become modeled integer nanoseconds.
    """
    if not campaigns or not all(campaigns):
        raise ValueError("campaigns must be a non-empty list of non-empty "
                         "window lists")
    windows = [w for c in campaigns for w in c]
    _validate_advancing(windows)
    stream_hi = windows[-1][1]
    anchors = [(c[0][0], stream_hi) for c in campaigns]
    if cost_model is not None:
        price = cost_model.hop_cost
        first_anchor = cost_model.anchor_cost(store.window_size(*anchors[0]))
    else:
        price = lambda edges: _instability_volume(edges, stable_milli)
        first_anchor = store.window_size(*anchors[0])
    slide = padding = 0
    for campaign, anchor in zip(campaigns, anchors):
        deltas = [price(hop_added_edges(store, anchor, w)) for w in campaign]
        slide += sum(deltas)
        bucket = lane_bucket(len(campaign), data_extent)
        padding += (bucket - len(campaign)) * max(deltas)
    anchor_edges = first_anchor + sum(
        price(hop_added_edges(store, prev, cur))
        for prev, cur in zip(anchors, anchors[1:]))
    return CampaignPlan(campaigns, anchors,
                        lane_budget if lane_budget is not None
                        else max(map(len, campaigns)),
                        data_extent, slide, anchor_edges, padding,
                        stable_milli=stable_milli, cost_model=cost_model)


def optimal_campaigns(store: SnapshotStore, windows: "list[Window]", *,
                      lane_budget: int = 8,
                      data_extent: int = 1,
                      stable_milli: int = 0,
                      cost_model=None) -> CampaignPlan:
    """Δ-volume-minimal campaign partition of an advancing window sequence.

    The streaming analogue of ``optimal_plan``'s interval DP over grid
    hops: where the TG planner chooses which hops to share *within* one
    launch tree, this DP chooses where to CUT the stream into campaigns —
    the "how much to share per launch" decision PR 4 left to a fixed
    ``campaign_width``. Suffix DP over cut points, both cost terms built
    from the same ``hop_added_edges`` atom:

    .. code-block:: text

        f(N) = 0
        f(j) = min over i in (j, min(j+lane_budget, N)]:
                 slideΔ(j, i)                       # Σ |T(w)| − |T(a_j)|
               + pad(j, i)                          # masked pow2 lanes
               + (|T(a_i)| − |T(a_j)|  if i < N)    # anchor hop into next
               + f(i)
        total = |T(a_0)| + f(0)          # a_j = (lo_j, stream_hi)

    The trade the DP resolves: wider campaigns anchor earlier (smaller
    ``|T(a_j)|``), so every window in them streams MORE slide Δ — but they
    pay fewer anchor hops and amortize the pow2 lane bucket better;
    ``lane_budget`` caps the width (device memory for one stacked launch),
    and ``data_extent`` makes the pad term mesh-aware (a campaign always
    launches a lane count divisible by the mesh's ``data`` axis). Runs in
    O(N · lane_budget) after the size table is built.

    Guarantee (property-tested): the returned plan's ``total_edges`` is
    ≤ that of EVERY fixed-width chunking with width ≤ ``lane_budget``,
    fixed widths being points in the DP's search space. ``stable_milli``
    applies the instability discount to every hop atom exactly as
    ``campaign_volume`` does (same :func:`_instability_volume` call per
    atom), so the DP's cost equals the partition's price and the auto ≤
    fixed-width guarantee holds under any discount. A ``cost_model``
    substitutes ``cost_model.hop_cost`` for that atom in BOTH the DP and
    the returned plan's pricing (``campaign_volume(..., cost_model=...)``),
    preserving the same DP-equals-price exactness — so the calibrated plan
    is never worse than any other partition *under the model*, including
    the raw-count plan re-priced by it.
    """
    windows = [tuple(w) for w in windows]
    if not windows:
        raise ValueError("need at least one window to plan campaigns")
    _validate_advancing(windows)
    if not isinstance(lane_budget, int) or lane_budget < 1:
        raise ValueError(f"lane_budget must be an int >= 1, "
                         f"got {lane_budget!r}")
    n = len(windows)
    stream_hi = windows[-1][1]
    anchor_size = [store.window_size(lo, stream_hi) for lo, _ in windows]
    window_size = [store.window_size(*w) for w in windows]
    price = (cost_model.hop_cost if cost_model is not None
             else lambda edges: _instability_volume(edges, stable_milli))

    INF = float("inf")
    f = [INF] * n + [0.0]
    cut: "list[int]" = [0] * n
    for j in range(n - 1, -1, -1):
        slide, widest = 0, 0
        for i in range(j + 1, min(j + lane_budget, n) + 1):
            delta = price(window_size[i - 1] - anchor_size[j])
            slide += delta
            widest = max(widest, delta)
            lanes = i - j
            pad = (lane_bucket(lanes, data_extent) - lanes) * widest
            hop = (price(anchor_size[i] - anchor_size[j]) if i < n else 0)
            cost = slide + pad + hop + f[i]
            if cost < f[j]:
                f[j], cut[j] = cost, i
    campaigns = []
    j = 0
    while j < n:
        campaigns.append(windows[j:cut[j]])
        j = cut[j]
    return campaign_volume(store, campaigns, data_extent=data_extent,
                           lane_budget=lane_budget,
                           stable_milli=stable_milli, cost_model=cost_model)


def _stream_qkey(semiring: Semiring, source: int, max_iters: int, gated: bool,
                 cg_split: int, track_parents: bool) -> tuple:
    """Anchor-state cache key: everything that selects the query.

    ``values`` of a converged state depend only on (semiring, source) — the
    rest is included conservatively so cached parents/behaviour always match
    the options of the run that would have rebuilt the state.
    """
    return (semiring.name, source, max_iters, gated, cg_split, track_parents)


@dataclasses.dataclass
class WindowStreamRun:
    """Result record of a streamed run: per-window values, the campaign
    partition, per-campaign anchor events (rebuild/hop/hit) and stats,
    and — in campaign_width="auto" mode — the chosen CampaignPlan."""

    results: dict[Window, jnp.ndarray]   # window -> values
    campaigns: "list[list[Window]]"
    anchors: "list[Window]"              # per-campaign anchor window
    # per-campaign anchor acquisition: "rebuild" (from-scratch fixpoint),
    # "hop" (incremental_additions from a cached covering state), or "hit"
    # (exact cached state — zero anchor work)
    anchor_events: "list[str]"
    anchor_stats: "list[StreamStats]"    # per-campaign anchor acquisition
    hop_stats: "list[StreamStats]"       # per-campaign stacked launch
    wall_s: float
    added_edges: int                     # total window-hop Δ volume
    anchor_delta_edges: int              # Δ volume of incremental anchor hops
    lane_layout: "list[tuple[int, int]]"
    # the CampaignPlan that chose the partition (campaign_width="auto" only)
    plan: "CampaignPlan | None" = None
    # measured stable fraction (‰) over all window hops in the run: the
    # share of vertex-lanes the stability analysis kept out of the seed
    # frontier (graph/stability.py; padding lanes excluded)
    stable_milli: int = 0

    @property
    def anchor_rebuilds(self) -> int:
        """Count of from-scratch anchor fixpoints in this run."""
        return self.anchor_events.count("rebuild")

    @property
    def anchor_hops(self) -> int:
        """Count of incremental anchor hops in this run."""
        return self.anchor_events.count("hop")

    @property
    def anchor_hits(self) -> int:
        """Count of exact anchor cache hits (zero anchor work)."""
        return self.anchor_events.count("hit")


def _acquire_anchor_state(store: SnapshotStore, qkey: tuple, anchor: Window,
                          semiring: Semiring, source: int, max_iters: int,
                          gated: bool, cg_split: int, track_parents: bool,
                          seed: str = "instability", fused_k: int = 1):
    """Anchor state via cache hit, incremental hop, or from-scratch rebuild.

    Returns ``(anchor_view, state, stats, event, delta_edges)`` —
    ``delta_edges`` is the hop's Δ volume (0 on hit/rebuild). The view's
    blocks UNION to exactly T(anchor) in every case (anchor view on
    hit/rebuild, cover view ⊕ hop Δ after a hop) — per-sweep reductions are
    block-partition invariant, so downstream campaign results do not depend
    on which path ran. The acquired state is (re-)cached under the anchor's
    "AS" tag. ``fused_k`` only shapes the hop/rebuild launches (bit-identical
    states at any value), which is why it is a launch option and NOT part of
    ``qkey`` — states stay shareable across fused chunk sizes. A hop runs in
    the span ``cg.anchor.hop``, a rebuild in ``cg.anchor.rebuild``.
    """
    t0 = time.perf_counter()
    state = store.anchor_state_get(qkey, anchor)
    if state is not None:
        view = _anchor_view(store, anchor, cg_split)
        return view, state, StreamStats(time.perf_counter() - t0, 0.0, 0), \
            "hit", 0
    cover = store.anchor_state_cover(qkey, anchor)
    if cover is not None:
        cover_window, cover_state = cover
        with span("cg.anchor.hop") as sp:
            delta = store.delta_block(cover_window, anchor)
            view = _anchor_view(store, cover_window, cg_split).extended(delta)
            res = incremental_additions(
                view, delta, semiring, cover_state.values, cover_state.parent,
                max_iters, gated=gated, track_parents=track_parents,
                seed=seed, fused_k=fused_k)
            host_sync(res.values)
            stats = StreamStats(time.perf_counter() - t0,
                                float(res.edge_work), int(res.iterations))
            size = store.window_size(*anchor)
            delta_edges = size - store.window_size(*cover_window)
            if sp.live:
                sp.set(**_sweep_counts(stats, 1, view.blocks, size, (delta,),
                                       delta_edges))
        state = store.anchor_state_put(qkey, anchor, extract_state(res))
        return view, state, stats, "hop", delta_edges
    anchor_view, base, base_stats = _anchor_base(
        store, anchor, semiring, source, max_iters, gated, cg_split,
        track_parents, fused_k)
    state = store.anchor_state_put(qkey, anchor, extract_state(base))
    return anchor_view, state, base_stats, "rebuild", 0


# ---------------------------------------------------------------------------
# Anchor chains: overlapping streams sharing one anchor-state sequence.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AnchorChain:
    """A named, refcounted chain of nested anchor states shared by streams.

    One advancing stream leaves behind a *chain* of converged anchor states
    in the store's "AS" family — interval-nested, each reachable from the
    previous by pure additions. A second stream over an overlapping window
    region can hop off those same states instead of rebuilding its own
    anchors from scratch: that is the paper's shared-additions idea applied
    ACROSS streams, one level up from the per-stream reuse PR 4 built.

    The chain object adds the lifecycle the bare cache cannot express:

    * **Registration.** Streams :meth:`register` by name; pass
      ``chain=`` to ``run_window_stream_batched`` and the scheduler
      records every anchor it acquires as a chain link
      (:meth:`observe`) and reports the stream's progress
      (:meth:`advance`).
    * **Pinning (the refcount).** A link is pinned in the store
      (``SnapshotStore.pin``) while ANY registered stream is still behind
      it — behind meaning the stream's last consumed anchor-lo has not
      passed the link's lo, so the link may yet seed one of its hops.
      Pinned links survive LRU pressure and ``release``; once every
      registered stream advances past a link it is PRUNED from the chain
      and its state returns to the LRU, so chain bookkeeping stays
      O(live links) over an unbounded stream. :meth:`unregister` a
      finished stream or its pins leak. (With no streams registered the
      links stay listed — unpinned — so a later stream can still find
      the chain via :func:`select_chain`.)
    * **Cover selection.** :meth:`cover` returns the tightest chain link
      covering a window (same tightest-|T| rule as
      ``SnapshotStore.anchor_state_cover``, restricted to this chain's
      links); :func:`select_chain` picks among several chains the one
      whose cover is tightest — how a new stream finds the chain to
      register against.

    Pinning never changes values — only whether a lagging stream pays a
    hit/hop (link retained) or a rebuild (link evicted). The chain binds to
    the first query key it serves; overlapping streams share a chain only
    when their query (semiring, source, options) agrees, else
    :meth:`bind` raises.
    """

    store: SnapshotStore
    name: str = "chain"
    qkey: "tuple | None" = None
    links: "list[Window]" = dataclasses.field(default_factory=list)
    _positions: "dict[str, int | None]" = dataclasses.field(
        default_factory=dict)
    _pinned: "set[Window]" = dataclasses.field(default_factory=set)

    def bind(self, qkey: tuple) -> "AnchorChain":
        """Bind the chain to a query key (first use wins, mismatch raises)."""
        if self.qkey is None:
            self.qkey = qkey
        elif self.qkey != qkey:
            raise ValueError(
                f"chain {self.name!r} is bound to query key {self.qkey!r}; "
                f"a stream with query key {qkey!r} cannot share it")
        return self

    @staticmethod
    def _member(stream: "WindowStream | str") -> str:
        return stream if isinstance(stream, str) else stream.name

    def register(self, stream: "WindowStream | str") -> "AnchorChain":
        """Add a stream to the chain (idempotent); pins every current link
        until the stream advances past it."""
        name = self._member(stream)
        if name not in self._positions:
            self._positions[name] = None   # behind everything
            self._repin()
        return self

    def unregister(self, stream: "WindowStream | str") -> None:
        """Remove a stream; links only it was behind unpin (and, while
        other streams remain registered, are pruned)."""
        name = self._member(stream)
        if name not in self._positions:
            raise ValueError(f"stream {name!r} is not registered with "
                             f"chain {self.name!r}")
        del self._positions[name]
        self._repin()

    def registered(self) -> "list[str]":
        """Names of currently registered streams, sorted."""
        return sorted(self._positions)

    def cover(self, window: Window) -> "Window | None":
        """Tightest chain link whose interval covers ``window`` (else None).

        Same rule as ``SnapshotStore.anchor_state_cover`` — both route
        through ``tightest_cover`` — restricted to this chain's links.
        """
        return tightest_cover(self.links, tuple(window),
                              self.store.window_size)

    def observe(self, anchor: Window) -> None:
        """Record an acquired anchor state as a chain link (scheduler hook)."""
        anchor = tuple(anchor)
        if anchor not in self.links:
            self.links.append(anchor)
            self.links.sort(key=lambda w: (w[0], -w[1]))
            self._repin()

    def advance(self, stream: "WindowStream | str", anchor: Window) -> None:
        """Report a stream's last consumed anchor; passed links unpin."""
        name = self._member(stream)
        if name not in self._positions:
            raise ValueError(f"stream {name!r} is not registered with "
                             f"chain {self.name!r}")
        self._positions[name] = anchor[0]
        self._repin()

    def _repin(self) -> None:
        """Reconcile store pins with the is-any-stream-behind rule.

        While at least one stream is registered, links every stream has
        passed are also PRUNED from the chain (they can never seed a
        registered stream's hop again, and per-campaign ``cover``/pin
        bookkeeping must stay O(live links), not O(stream lifetime)) —
        their cached states simply return to the LRU. With no streams
        registered the links are kept (unpinned) so a later stream can
        still discover the chain via ``select_chain``.
        """
        want = set()
        if self._positions:
            positions = list(self._positions.values())
            want = {link for link in self.links
                    if any(pos is None or pos <= link[0]
                           for pos in positions)}
            self.links = [link for link in self.links if link in want]
        for link in want - self._pinned:
            self.store.pin(anchor_tag(self.qkey, link))
        for link in self._pinned - want:
            self.store.unpin(anchor_tag(self.qkey, link))
        self._pinned = want


def select_chain(chains: "list[AnchorChain]", window: Window,
                 qkey: "tuple | None" = None) -> "AnchorChain | None":
    """The chain whose links give the tightest cover of ``window``.

    How an arriving stream picks its chain: among ``chains`` (optionally
    filtered to a query key), the one holding the largest-|T| covering
    link — the cover that minimizes the stream's first anchor hop. Returns
    ``None`` when no chain covers the window (the stream then starts its
    own chain with one rebuild).
    """
    best, best_size = None, -1
    for chain in chains:
        if qkey is not None and chain.qkey is not None and chain.qkey != qkey:
            continue
        link = chain.cover(window)
        if link is not None:
            size = chain.store.window_size(*link)
            if size > best_size:
                best, best_size = chain, size
    return best


def run_window_stream_batched(
    store: SnapshotStore,
    semiring: Semiring,
    source: int,
    width: int | None = None,
    *,
    windows: "list[Window] | None" = None,
    stream: WindowStream | None = None,
    step: int = 1,
    start: int = 0,
    campaign_width: "int | str | None" = None,
    lane_budget: int = 8,
    chain: "AnchorChain | None" = None,
    max_iters: int = 10_000,
    gated: bool = False,
    cg_split: int = 1,
    track_parents: bool = False,
    mesh=None,
    seed: str = "instability",
    stable_milli: int = 0,
    cost_model=None,
    fused_k: int = 1,
) -> WindowStreamRun:
    """Streaming slide campaigns with incremental anchor maintenance.

    Consumes an advancing window sequence (``stream.take()``, an explicit
    ``windows`` list, or a ``slide_windows`` plan from ``width``), cuts it
    into campaigns of ``campaign_width`` windows (default 4; a
    ``WindowStream`` carries its own width, so passing both together is an
    error), and runs each campaign as
    ONE masked pow2-lane ``incremental_additions_batched`` launch (the
    ``run_window_slide_batched`` machinery, sharded over ``data`` when a
    mesh is given).

    ``campaign_width="auto"`` hands the partition to ``optimal_campaigns``:
    an interval DP over cut points minimizing total Δ-edge volume (slide
    hops + anchor hops + the pow2 masked-lane penalty), capped at
    ``lane_budget`` windows per launch and mesh-aware (the pad term uses
    the mesh's ``data`` extent). The chosen :class:`CampaignPlan` is
    returned on the run's ``plan`` field; ``lane_budget`` is only read in
    auto mode.

    ``chain=`` (requires ``stream=``) shares anchor states across
    OVERLAPPING streams via an :class:`AnchorChain`: the stream registers
    with the chain, every anchor it acquires becomes a chain link, and
    links stay pinned against eviction while any registered stream is
    still behind them — so a second stream over the same region hops off
    the first stream's anchors (strictly fewer rebuilds than running
    solo) with bit-identical values.

    Campaign k anchors at ``(lo_k, stream_hi)`` — its windows' span widened
    to the stream's last snapshot. Widening is what makes the anchor chain
    monotone: campaign k+1's anchor interval is nested in campaign k's, so
    its common graph is reachable from k's converged state by PURE
    ADDITIONS, and the scheduler seeds it with one incremental hop instead
    of recomputing from the base snapshot. Anchor states are cached in the
    store's "AS" LRU family, so only the first campaign (or a campaign
    whose predecessors were evicted, or one whose stream has advanced past
    every cached cover) pays a from-scratch rebuild.

    Results are bit-identical to running ``run_window_slide_batched`` cold
    per campaign with the same anchors; the streamed path just performs
    strictly fewer anchor rebuilds (1 + evictions vs one per campaign).

    ``seed`` picks the frontier-seeding mode for every hop in the run
    (``"instability"`` — the stable-vertex analysis, default — or
    ``"delta"``, the full-Δ baseline; values bit-identical either way).
    ``stable_milli`` is the PLANNER HINT: the modeled stable fraction (‰)
    ``optimal_campaigns`` discounts its Δ-volume atoms by in auto mode
    (e.g. a fraction measured by a prior run over the same load); the
    run's own measured fraction comes back on the result's
    ``stable_milli`` field regardless.

    ``cost_model`` upgrades the auto-mode planner from the discounted
    edge-count proxy to measured prices (core/costmodel.SweepCostModel,
    e.g. from ``evolve --calibrate``) — it is forwarded to
    ``optimal_campaigns`` and recorded on the returned plan; ignored
    outside auto mode. ``fused_k`` is the engine's fused-chunk launch
    option, threaded to every anchor acquisition and stacked slide launch
    in the run; results are bit-identical at any value, so it is NOT part
    of the anchor-state cache key.
    """
    t_all = time.perf_counter()
    if stream is not None:
        if windows is not None or width is not None:
            raise ValueError("pass stream= alone, not with width=/windows=")
        if campaign_width is not None:
            raise ValueError("campaign_width= conflicts with stream=: the "
                             "WindowStream carries its own campaign width")
        windows = stream.take()
        campaign_width = stream.campaign_width
    else:
        if chain is not None:
            raise ValueError("chain= requires stream=: an AnchorChain tracks "
                             "named WindowStreams, so anonymous window lists "
                             "cannot register against one")
        if campaign_width is None:
            campaign_width = 4
        if windows is None:
            if width is None:
                raise ValueError("pass width=, windows= or stream=")
            windows = slide_windows(store.seq.num_snapshots, width, step=step,
                                    start=start)
        windows = [tuple(w) for w in windows]
        _validate_advancing(windows)
    qkey = _stream_qkey(semiring, source, max_iters, gated, cg_split,
                        track_parents)
    if chain is not None:
        if chain.store is not store:
            raise ValueError("chain= must share the run's SnapshotStore — "
                             "anchor states live in the store's AS family")
        chain.bind(qkey).register(stream)
    if not windows:
        return WindowStreamRun({}, [], [], [], [], [],
                               time.perf_counter() - t_all, 0, 0, [])
    plan = None
    if campaign_width == CAMPAIGN_AUTO:
        plan = optimal_campaigns(
            store, windows, lane_budget=lane_budget,
            data_extent=mesh.shape["data"] if mesh is not None else 1,
            stable_milli=stable_milli, cost_model=cost_model)
        campaigns = plan.campaigns
    else:
        campaigns = stream_campaigns(windows, campaign_width)
    stream_hi = windows[-1][1]

    results: dict[Window, jnp.ndarray] = {}
    anchors: "list[Window]" = []
    anchor_events: "list[str]" = []
    anchor_stats: "list[StreamStats]" = []
    hop_stats: "list[StreamStats]" = []
    lane_layout: "list[tuple[int, int]]" = []
    added_edges = 0
    anchor_delta_edges = 0
    unstable_counts: "list[np.ndarray]" = []
    for campaign in campaigns:
        anchor = (min(i for i, _ in campaign), stream_hi)
        anchor_view, state, stats, event, delta_edges = _acquire_anchor_state(
            store, qkey, anchor, semiring, source, max_iters, gated, cg_split,
            track_parents, seed=seed, fused_k=fused_k)
        if chain is not None:
            chain.observe(anchor)   # pin before any later put can evict it
        anchors.append(anchor)
        anchor_events.append(event)
        anchor_stats.append(stats)
        anchor_delta_edges += delta_edges
        res, bucket, stats = _slide_launch(
            store, semiring, anchor_view, state, campaign, anchor,
            max_iters=max_iters, gated=gated, track_parents=track_parents,
            mesh=mesh, seed=seed, fused_k=fused_k)
        hop_stats.append(stats)
        lane_layout.append((len(campaign), bucket))
        unstable_counts.append(np.asarray(res.unstable)[:len(campaign)])
        for lane, wnd in enumerate(campaign):
            results[wnd] = res.values[lane]
        added_edges += _slide_added_edges(store, campaign, anchor)
        if chain is not None:
            chain.advance(stream, anchor)   # links all streams passed unpin
    return WindowStreamRun(results, campaigns, anchors, anchor_events,
                           anchor_stats, hop_stats,
                           time.perf_counter() - t_all, added_edges,
                           anchor_delta_edges, lane_layout, plan,
                           stable_milli=stable_fraction_milli(
                               np.concatenate(unstable_counts),
                               store.num_nodes))
