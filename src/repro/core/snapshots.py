"""Snapshot store: window intersections, Δ-batches, mutation-free views.

This is the paper's graph representation (§2, third contribution): the
CommonGraph of any window plus immutable Δ-batches. Key facts exploited:

* For nested windows ``[i..j] ⊇ [a..b]``: ``T(i,j) ⊆ T(a,b)`` — a wider
  window's common graph is a subgraph of a narrower one's. Hence descending
  the Triangular Grid only ever *adds* edges, and
  ``|Δ(T(i,j) → T(a,b))| = |T(a,b)| − |T(i,j)|``.
* Snapshots are the diagonal: ``S_i = T(i,i)``.

Set algebra runs host-side on sorted int64 key arrays (this is the part of
the system that, at cluster scale, becomes a distributed sort/merge over the
ingest pipeline; on one host numpy's merge-based set ops are the right tool).
Device-side execution consumes only the padded immutable blocks.

Host preparation runs in two spans (``repro.runtime.spans``):
``cg.store.keys`` around set algebra (a ``window_keys`` or ``delta_keys``
miss) and ``cg.store.blocks`` around building a block on a cache miss (key
decode, weights, dst sort, padding, upload). Cache hits open none. A
``delta_stack`` miss's ``cg.store.blocks`` span counts its lanes whose Δ
keys came from the memo (``delta_hit``) and were computed (``delta_miss``).

Store contract (what every executor may assume):

* **Pure cache.** Every block is a pure function of ``(seq, tag)``: evicting
  a block and re-fetching it rebuilds a bit-identical array (same edges,
  same dst-sort order, same padding). Eviction can therefore never change
  any executor's result, only its memory/rebuild cost.
* **Bounded device memory (opt-in).** ``cache_bytes`` puts the device-block
  cache under an LRU byte budget. The batched executors retain every
  shape-bucketed ``delta_stack`` lane buffer alongside the per-hop "D"
  blocks covering the same edges; memory-tight accelerators comparing both
  executors bound that with the budget, or drop a whole block family
  explicitly via :meth:`SnapshotStore.release`.
* **Shape bucketing.** Blocks are padded to granule buckets (pow2 by
  default) so jit trace shapes depend only on the bucket, not exact ragged
  sizes (see ``graph/edgeset.py``); stacked lane buffers additionally
  bucket their LANE axis (``delta_stack``/``slide_stack`` ``num_lanes=``,
  trailing lanes all-sentinel) so trace keys are ``(pow2 lanes, pow2
  width)`` and the lane axis always divides a mesh's ``data`` extent.
  Host-side key arrays (``window_keys``) are never evicted — they are the
  cheap part and keep rebuilds exact.
* **Δ key memo.** ``delta_keys`` computes each (parent, child) hop's
  addition keys once and returns the same read-only array on every later
  call: a pure function of the two windows' keys, so every Δ block and
  answer is unchanged. Like the window keys it is host-side and never
  evicted by ``cache_bytes`` or ``release``; ``compact`` drops the entries
  whose parent window reaches below the new horizon.
* **Anchor-state family ("AS" tags).** Converged anchor query states
  (``QueryState``) live in the SAME LRU alongside edge blocks — the first
  cross-launch reuse: a streaming campaign scheduler (core/window.py) seeds
  campaign k+1's anchor from campaign k's cached state instead of
  recomputing from the base snapshot. Values of a cached state are a pure
  function of ``(window, query key)`` (the monotone rounded fixpoint is
  unique), so eviction again costs only recompute, never correctness.
* **Pinning (refcounted eviction exemption).** ``pin``/``unpin`` exempt a
  tag from LRU eviction and from ``release``. The anchor-chain scheduler
  (core/window.py::AnchorChain) pins the chain links its registered
  streams are still behind, so a memory-tight store cannot evict a state a
  lagging overlapping stream is about to hop from; once every stream has
  advanced past a link it is unpinned and ages out normally. Pinning never
  affects results — only which path (hit/hop/rebuild) acquires a state.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict

import numpy as np

from repro.graph.edgeset import (
    EdgeBlock,
    EdgeView,
    keys_to_edges,
    make_block,
    stack_delta_blocks,
)
from repro.graph.generators import EvolvingSequence
from repro.runtime.spans import span


def tightest_cover(candidates, window, size_fn):
    """Largest-|T| candidate window covering ``window`` (None if none).

    THE cover rule, in one place: a state converged on ``(ci, cj)`` can
    warm-start ``window = (a, b)`` iff ``ci <= a and b <= cj`` (its common
    graph is a subgraph), and among covers the largest ``size_fn(ci, cj)``
    minimizes the hop's Δ volume. Shared by the store's AS-family scan
    (:meth:`SnapshotStore.anchor_state_cover`) and the anchor-chain link
    selection (core/window.py ``AnchorChain.cover``) so the two can never
    disagree about which cover is tightest.
    """
    a, b = window
    best, best_size = None, -1
    for cand in candidates:
        ci, cj = cand
        if ci <= a and b <= cj:
            size = size_fn(ci, cj)
            if size > best_size:
                best, best_size = cand, size
    return best


def anchor_tag(qkey: tuple, window: "tuple[int, int]") -> tuple:
    """The canonical "AS"-family cache tag for an anchor state.

    ``("AS", qkey, (i, j))`` — THE tag constructor for anchor states, used
    by the store's own ``anchor_state_*`` accessors and by external
    pin/unpin callers (core/window.py ``AnchorChain``). Tags are cache
    identity: a hand-built tuple that drifts from this shape (family
    string, qkey structure, list-vs-tuple window) silently misses the
    cached entry or pins nothing, which is why graphlint rule G003 bans
    literal tag construction outside this module.
    """
    return ("AS", qkey, tuple(window))


@dataclasses.dataclass(frozen=True)
class CompactionStats:
    """What one :meth:`SnapshotStore.compact` call retired.

    ``horizon`` is the first snapshot index kept live after clamping to
    every registered floor and pinned "AS" anchor; ``retired`` counts
    snapshots actually freed this call; ``freed_edges`` sums the host-side
    key/Δ array entries released. ``retired == 0`` (horizon already live,
    or clamped all the way back) is a legal no-op result.
    """

    horizon: int
    retired: int
    freed_edges: int


def _tag_min_index(tag: tuple) -> "int | None":
    """Smallest snapshot index a cached block tag depends on (None = keep).

    Families: ``("T", i, j)`` / ``("Ts", i, j, n, k)`` depend on ``i``;
    ``("D", parent, child)`` and ``("DS", lanes, *hops)`` on the smallest
    window low across their hop windows; ``("A", t)`` on transition ``t``
    (snapshot ``t`` → ``t+1``); ``("AS", qkey, (i, j))`` on ``i``. Unknown
    families are kept — compaction must never guess an entry stale.
    """
    fam = tag[0]
    if fam in ("T", "Ts"):
        return int(tag[1])
    if fam == "D":
        return min(int(tag[1][0]), int(tag[2][0]))
    if fam == "DS":
        return min(int(w[0]) for hop in tag[2:] for w in hop)
    if fam == "A":
        return int(tag[1])
    if fam == "AS":
        return int(tag[2][0])
    return None


def _block_nbytes(blk) -> int:
    # Cached entries that know their own footprint (engine QueryStates via
    # the ``nbytes`` hook) report it; raw EdgeBlocks are summed directly.
    n = getattr(blk, "nbytes", None)
    if n is not None:
        return int(n)
    return sum(int(a.size) * a.dtype.itemsize for a in blk)


class SnapshotStore:
    """Caches window common-graphs T(i,j) (key arrays) and device blocks.

    ``cache_bytes`` (default ``None`` = unbounded) bounds the device-block
    cache: least-recently-used blocks are dropped once the budget is
    exceeded (the block just built is always kept, even if it alone exceeds
    the budget — callers hold a reference to it anyway). ``release`` drops
    whole block families explicitly. Both are safe: re-fetching rebuilds
    bit-identical blocks from the retained host-side key arrays.
    """

    def __init__(self, seq: EvolvingSequence, granule: int = 4096,
                 pad_pow2: bool = True, cache_bytes: int | None = None):
        self.seq = seq
        self.num_nodes = seq.num_nodes
        self.granule = granule
        self.pad_pow2 = pad_pow2
        self.cache_bytes = cache_bytes
        self._t: dict[tuple[int, int], np.ndarray] = {
            (i, i): seq.snapshot_keys[i] for i in range(seq.num_snapshots)
        }
        self._deltas: dict[tuple, np.ndarray] = {}   # (parent, child) -> Δ keys
        self._blocks: OrderedDict[tuple, EdgeBlock] = OrderedDict()
        self._cached_nbytes = 0
        self._pins: dict[tuple, int] = {}   # tag -> refcount (see pin())
        self.evictions = 0  # lifetime count, for tests/benchmarks
        self.first_live = 0  # oldest non-retired snapshot (see compact())
        self._floors: dict[str, int] = {}   # name -> oldest index needed

    # -- block cache (LRU by bytes + explicit release) -------------------------

    @property
    def cached_nbytes(self) -> int:
        """Current device-block cache footprint (padded array bytes)."""
        return self._cached_nbytes

    def _cache_get(self, tag: tuple) -> EdgeBlock | None:
        blk = self._blocks.get(tag)
        if blk is not None:
            self._blocks.move_to_end(tag)
        return blk

    def _cache_put(self, tag: tuple, blk: EdgeBlock) -> EdgeBlock:
        # Overwriting an existing tag must displace the old entry's bytes,
        # or cached_nbytes drifts upward and triggers spurious evictions.
        old = self._blocks.pop(tag, None)
        if old is not None:
            self._cached_nbytes -= _block_nbytes(old)
        self._blocks[tag] = blk
        self._cached_nbytes += _block_nbytes(blk)
        if self.cache_bytes is not None and self._cached_nbytes > self.cache_bytes:
            # LRU order, skipping pinned tags and the entry just stored (the
            # caller holds a reference to it anyway).
            for old_tag in list(self._blocks):
                if self._cached_nbytes <= self.cache_bytes \
                        or len(self._blocks) <= 1:
                    break
                if old_tag == tag or self._pins.get(old_tag):
                    continue
                self._cached_nbytes -= _block_nbytes(self._blocks.pop(old_tag))
                self.evictions += 1
        return blk

    def pin(self, tag: tuple) -> None:
        """Exempt a cached entry from LRU eviction (refcounted).

        Pins nest: each ``pin`` must be matched by one :meth:`unpin` before
        the entry returns to normal LRU management. Pinning is by tag, so it
        survives the entry being overwritten (re-``put`` under the same tag)
        and is legal before the entry exists — the anchor-chain scheduler
        (core/window.py::AnchorChain) pins "AS" states its registered
        streams are still behind. Pinned entries still count toward
        ``cache_bytes``; :meth:`release` also skips them.
        """
        self._pins[tag] = self._pins.get(tag, 0) + 1

    def unpin(self, tag: tuple) -> None:
        """Drop one pin refcount; at zero the entry rejoins the LRU."""
        n = self._pins.get(tag, 0) - 1
        if n < 0:
            raise ValueError(f"unpin without matching pin for tag {tag!r}")
        if n == 0:
            del self._pins[tag]
        else:
            self._pins[tag] = n

    def pinned_tags(self) -> "set[tuple]":
        """Tags currently exempt from eviction (for tests/diagnostics)."""
        return set(self._pins)

    def pin_count(self, tag: tuple) -> int:
        """Current pin refcount of ``tag`` (0 when unpinned).

        Diagnostic mirror of the refcount :meth:`pin`/:meth:`unpin`
        maintain — the query-service soak tests audit that every pin taken
        while streams were live has drained back to zero after
        ``unregister``.
        """
        return self._pins.get(tag, 0)

    def release(self, kinds: "tuple[str, ...] | None" = None) -> int:
        """Drop cached device blocks; returns the number of bytes released.

        ``kinds`` filters by tag family — e.g. ``("DS",)`` drops only the
        stacked ``delta_stack`` buffers the batched executors built, leaving
        the sequential executors' per-hop "D" blocks warm, and ``("AS",)``
        drops cached anchor query states (the streaming scheduler then
        rebuilds its next anchor cold). ``None`` drops everything except
        pinned entries (:meth:`pin`) — a chain link some registered stream
        still needs cannot be dropped out from under it. Host-side key
        arrays are never dropped, so subsequent fetches rebuild
        bit-identical blocks.
        """
        if isinstance(kinds, str):  # release("DS") must not match family "D"
            kinds = (kinds,)
        drop = [t for t in self._blocks
                if (kinds is None or t[0] in kinds) and not self._pins.get(t)]
        freed = 0
        for t in drop:
            freed += _block_nbytes(self._blocks.pop(t))
        self._cached_nbytes -= freed
        return freed

    # -- anchor-state cache (cross-launch reuse, streaming campaigns) ----------
    #
    # Tags are ("AS", qkey, (i, j)): qkey identifies the query (semiring,
    # source, options — see core/window.py::_stream_qkey), (i, j) the anchor
    # window the state converged on. States share the LRU byte budget with
    # edge blocks: a cached anchor family can be evicted mid-stream, which
    # costs the scheduler one rebuild and never changes results (values are
    # the unique monotone fixpoint of (window, qkey)).

    def anchor_state_get(self, qkey: tuple, window: "tuple[int, int]"):
        """Cached converged QueryState for exactly this (qkey, window)."""
        return self._cache_get(anchor_tag(qkey, window))

    def anchor_state_put(self, qkey: tuple, window: "tuple[int, int]", state):
        """Cache a converged anchor state (LRU-participating, "AS" family)."""
        return self._cache_put(anchor_tag(qkey, window), state)

    def anchor_state_cover(self, qkey: tuple, window: "tuple[int, int]"):
        """Tightest cached anchor state whose window COVERS ``window``.

        A state converged on a super-window (i, j) ⊇ (a, b) warm-starts
        T(a, b) by pure additions (T(i,j) ⊆ T(a,b)); among cached covers the
        tightest — largest |T(cover)| — minimizes the Δ volume of the hop.
        Returns ``(cover_window, state)`` or ``None``. The exact window
        itself is excluded; use :meth:`anchor_state_get` for hits.
        """
        window = tuple(window)
        best = tightest_cover(
            [tag[2] for tag in self._blocks
             if tag[0] == "AS" and tag[1] == qkey and tag[2] != window],
            window, self.window_size)
        if best is None:
            return None
        return best, self._cache_get(anchor_tag(qkey, best))  # touches LRU

    # -- window intersections -------------------------------------------------

    def window_keys(self, i: int, j: int) -> np.ndarray:
        """Sorted keys of T(i,j) = ⋂_{k∈[i..j]} S_k (cached, built left-to-right).

        Iterative from the widest cached prefix (i, k): a cold (0, n−1)
        request on a multi-thousand-snapshot sequence must not hit the
        Python recursion limit. (i, i) is always cached, so the prefix scan
        terminates.
        """
        if (i, j) in self._t:
            return self._t[(i, j)]
        if j < i:
            raise ValueError(f"window ({i}, {j}) is empty: need i <= j")
        if i < self.first_live:
            raise ValueError(
                f"window ({i}, {j}) reaches below first_live="
                f"{self.first_live}: snapshot {i} was retired by compact()")
        k = j
        while (i, k) not in self._t:
            k -= 1
        cur = self._t[(i, k)]
        with span("cg.store.keys"):
            for m in range(k + 1, j + 1):
                cur = np.intersect1d(cur, self.seq.snapshot_keys[m],
                                     assume_unique=True)
                self._t[(i, m)] = cur
        return cur

    def window_size(self, i: int, j: int) -> int:
        """|T(i, j)| — the edge count every Δ-volume cost model uses."""
        return int(self.window_keys(i, j).shape[0])

    def delta_keys(self, parent: tuple[int, int], child: tuple[int, int]) -> np.ndarray:
        """Edges added when descending T(parent) → T(child); child ⊆ parent window.

        Memoised per (parent, child): the first call computes the set
        difference, every later one returns the same read-only array.
        """
        pi, pj = parent
        ci, cj = child
        if not (pi <= ci and cj <= pj):
            raise ValueError(f"child window {child} not nested in parent {parent}")
        memo = (tuple(parent), tuple(child))
        keys = self._deltas.get(memo)
        if keys is None:
            child_keys = self.window_keys(ci, cj)
            parent_keys = self.window_keys(pi, pj)
            with span("cg.store.keys"):
                keys = np.setdiff1d(child_keys, parent_keys, assume_unique=True)
            keys.flags.writeable = False
            self._deltas[memo] = keys
        return keys

    # -- device blocks ---------------------------------------------------------

    def block_for_keys(self, keys: np.ndarray, tag: tuple) -> EdgeBlock:
        """Immutable padded device block for a key set (cached by tag)."""
        blk = self._cache_get(tag)
        if blk is not None:
            return blk
        with span("cg.store.blocks"):
            src, dst = keys_to_edges(keys, self.num_nodes)
            w = self.seq.weights_for(keys)
            blk = make_block(src, dst, w, self.num_nodes,
                             granule=self.granule, pad_pow2=self.pad_pow2)
        return self._cache_put(tag, blk)

    def window_block(self, i: int, j: int) -> EdgeBlock:
        """T(i, j) as a single cached device block (tag family "T")."""
        return self.block_for_keys(self.window_keys(i, j), ("T", i, j))

    def window_view_split(self, i: int, j: int, n_blocks: int) -> EdgeView:
        """Window view split into src-contiguous sub-blocks.

        Keys are src-major, so each sub-block covers a narrow source range —
        which makes the engine's block gating (frontier ∩ block sources)
        highly selective during incremental hops (EXPERIMENTS.md §Perf).
        """
        keys = self.window_keys(i, j)
        chunks = np.array_split(keys, n_blocks)
        blocks = tuple(
            self.block_for_keys(c, ("Ts", i, j, n_blocks, k))
            for k, c in enumerate(chunks) if c.size)
        return EdgeView(blocks, self.num_nodes)

    def delta_block(self, parent: tuple[int, int], child: tuple[int, int]) -> EdgeBlock:
        """The addition batch of one nested-window hop (tag family "D")."""
        return self.block_for_keys(self.delta_keys(parent, child),
                                   ("D", parent, child))

    def delta_stack(
        self, hops: "list[tuple[tuple[int, int], tuple[int, int]]]",
        num_lanes: int | None = None,
    ) -> EdgeBlock:
        """Stacked Δ-batches for several parent→child hops (one lane per hop).

        The lanes of one plan level are independent sibling hops; stacking
        them (shape-bucketed, see ``stack_delta_blocks``) turns the level
        into a single snapshot-axis launch of the batched engine. Cached by
        the hop list so re-running a plan rebuilds nothing.

        ``num_lanes`` buckets the LANE axis: the batched executors pass
        ``lane_bucket(len(hops), data_extent)`` so every stack's lane count
        is pow2 and mesh-divisible, with trailing all-sentinel masked lanes
        (see ``stack_delta_blocks``). The bucketed lane count is part of the
        cache tag, so trace keys — which follow the stacked shape — become
        ``(pow2 lanes, pow2 width)``.
        """
        tag = ("DS", num_lanes or len(hops)) + tuple(hops)
        blk = self._cache_get(tag)
        if blk is not None:
            return blk
        with span("cg.store.blocks") as sp:
            lanes, memo_size = [], len(self._deltas)
            for parent, child in hops:
                keys = self.delta_keys(parent, child)
                s, d = keys_to_edges(keys, self.num_nodes)
                lanes.append((s, d, self.seq.weights_for(keys)))
            blk = stack_delta_blocks(lanes, self.num_nodes,
                                     granule=self.granule,
                                     pad_pow2=self.pad_pow2,
                                     num_lanes=num_lanes)
            if sp.live:     # each computed Δ adds one memo entry
                misses = len(self._deltas) - memo_size
                sp.set(delta_hit=len(hops) - misses, delta_miss=misses)
        return self._cache_put(tag, blk)

    def snapshot_view(self, i: int) -> EdgeView:
        """Standalone single-block view of S_i (used by from-scratch baselines)."""
        return EdgeView((self.window_block(i, i),), self.num_nodes)

    def common_graph_view(self, i: int | None = None,
                          j: int | None = None) -> EdgeView:
        """Single-block view of T(i, j); defaults to the global common graph
        over the live range (``first_live`` .. last snapshot)."""
        if i is None:
            i = self.first_live
        if j is None:
            j = self.seq.num_snapshots - 1
        return EdgeView((self.window_block(i, j),), self.num_nodes)

    # -- change batches (for the KickStarter streaming baseline) ---------------

    def addition_block(self, t: int) -> EdgeBlock:
        """Edges added at transition t → t+1."""
        return self.block_for_keys(self.seq.additions[t], ("A", t))

    def deletion_keys(self, t: int) -> np.ndarray:
        """Keys deleted at transition t → t+1 (KickStarter baseline input)."""
        return self.seq.deletions[t]

    # -- live ingestion (core/ingest.py) ---------------------------------------
    #
    # The one write path that grows the store after construction. A live
    # store wraps a mutable sequence (ingest.LiveSequence); `ingest_cut`
    # appends one snapshot + canonical Δ pair per watermark cut, and
    # `compact` retires snapshots no registered window floor or pinned "AS"
    # anchor still needs. Graphlint rule G009 confines `ingest_cut` calls to
    # `Watermark.cut` and bans ad-hoc cache writes from ingestion paths.

    def ingest_cut(self, keys: np.ndarray, added: np.ndarray,
                   deleted: np.ndarray, common: "np.ndarray | None" = None,
                   common_lo: "int | None" = None) -> int:
        """Install one cut snapshot + Δ pair; returns its index.

        The ingestion write path (called from ``ingest.Watermark.cut`` —
        graphlint G009 flags any other caller): appends to the live
        sequence, registers the new diagonal ``(idx, idx)`` in the window
        cache (``window_keys``' prefix scan requires every diagonal), and,
        when the watermark passes its incrementally maintained common
        graph (``common`` spanning ``[common_lo .. idx]``), installs it so
        anchor queries at the live base pay no re-intersection. Requires a
        mutable sequence (``ingest.LiveSequence``); a frozen
        ``EvolvingSequence`` store is input-only and raises ``TypeError``.
        """
        append = getattr(self.seq, "append", None)
        if append is None:
            raise TypeError(
                "ingest_cut needs a mutable live sequence "
                "(ingest.LiveSequence); EvolvingSequence stores are "
                "precomputed inputs")
        idx = append(keys, added, deleted)
        self._t[(idx, idx)] = keys
        if common is not None and common_lo is not None and common_lo != idx:
            self._t[(common_lo, idx)] = common
        return idx

    def set_floor(self, name: str, index: int) -> None:
        """Register/advance a named compaction floor: "snapshots older than
        ``index`` are no longer needed by this consumer". ``compact`` clamps
        its horizon to the minimum registered floor, so a consumer that
        never advances its floor simply prevents retirement."""
        self._floors[name] = int(index)

    def drop_floor(self, name: str) -> None:
        """Withdraw a named floor (missing names are a no-op)."""
        self._floors.pop(name, None)

    @property
    def stored_edges(self) -> int:
        """Host-side edge entries currently stored (snapshot keys + Δ pairs).

        The compaction yardstick: ``compact`` must strictly shrink this
        when it retires anything (acceptance criterion of the ingestion
        PR). Retired entries are ``None`` and count zero.
        """
        seq = self.seq
        arrays = list(seq.snapshot_keys) + list(seq.additions) \
            + list(seq.deletions)
        return sum(int(a.shape[0]) for a in arrays if a is not None)

    def compact(self, before: "int | None" = None) -> CompactionStats:
        """Retire snapshots older than every consumer still needs.

        The horizon starts at ``before`` (default: the latest snapshot)
        and clamps DOWN to (a) every floor registered via
        :meth:`set_floor` — live window feeds keep the snapshots their
        unconsumed windows span — and (b) every pinned "AS" anchor's
        window low: a pinned anchor state is a promise some stream will
        hop from it, and the hop's Δ keys need the anchor window's
        intersection. Snapshots below the clamped horizon are freed
        (host key/Δ arrays become ``None`` placeholders so absolute
        indices never shift), stale window-cache entries, memoised Δ keys
        and device blocks referencing them are purged (pinned tags
        skipped — they are unreachable only until unpinned), and
        ``first_live`` advances.
        Requires a mutable live sequence, like :meth:`ingest_cut`.
        """
        seq = self.seq
        if not isinstance(seq.snapshot_keys, list):
            raise TypeError(
                "compact needs a mutable live sequence "
                "(ingest.LiveSequence); EvolvingSequence stores are "
                "precomputed inputs")
        horizon = seq.num_snapshots - 1 if before is None else int(before)
        for floor in self._floors.values():
            horizon = min(horizon, floor)
        for tag in self._pins:
            if tag[0] == "AS":
                horizon = min(horizon, int(tag[2][0]))
        horizon = max(horizon, self.first_live)
        freed = 0
        for i in range(self.first_live, horizon):
            freed += int(seq.snapshot_keys[i].shape[0])
            seq.snapshot_keys[i] = None
            if seq.additions[i] is not None:
                freed += int(seq.additions[i].shape[0])
                freed += int(seq.deletions[i].shape[0])
                seq.additions[i] = None
                seq.deletions[i] = None
        retired = horizon - self.first_live
        if retired:
            for w in [w for w in self._t if w[0] < horizon]:
                del self._t[w]
            for hop in [hop for hop in self._deltas if hop[0][0] < horizon]:
                del self._deltas[hop]   # the parent is the wider window
            for tag in list(self._blocks):
                low = _tag_min_index(tag)
                if low is not None and low < horizon \
                        and not self._pins.get(tag):
                    self._cached_nbytes -= _block_nbytes(
                        self._blocks.pop(tag))
            self.first_live = horizon
        return CompactionStats(horizon=horizon, retired=retired,
                               freed_edges=freed)

    # -- sliding windows (full-paper feature) -----------------------------------
    #
    # Sliding [i..j] → [i+1..j+1] is NOT deletion-free from the old apex:
    # T(i,j) ⊄ T(i+1,j+1) in general (an old-CG edge may be absent from
    # S_{j+1}). The sound anchor is any SUPER-window apex — widest available
    # is the global CG, which is ⊆ every window's CG — from which every new
    # window apex is reachable by additions only. ``slide_block`` packages
    # that hop; it is just delta_block with the anchor made explicit, so all
    # nesting validation and caching carry over.

    def slide_block(self, new_window: tuple[int, int],
                    anchor: tuple[int, int] | None = None) -> EdgeBlock:
        """Addition batch hopping the anchor apex state to ``new_window``'s apex.

        ``anchor`` defaults to the global window (always a valid super-window).
        The anchor's query state warm-starts the new apex exactly (monotone
        additions only) — see tests/test_core.py::test_sliding_window_hop.
        """
        if anchor is None:
            anchor = (self.first_live, self.seq.num_snapshots - 1)
        return self.delta_block(anchor, new_window)

    def slide_stack(self, windows: "list[tuple[int, int]]",
                    anchor: tuple[int, int] | None = None,
                    num_lanes: int | None = None) -> EdgeBlock:
        """Stacked slide deltas: one lane per window, all hopping from ``anchor``.

        The batched window-slide executor's block assembly: every
        ``slide_block(window, anchor)`` becomes one lane of a single stacked
        EdgeBlock (shape-bucketed like any ``delta_stack``), so the whole
        slide runs as ONE ``incremental_additions_batched`` launch
        (core/window.py). ``anchor`` defaults to the global window;
        ``num_lanes`` buckets the lane axis exactly as in ``delta_stack``.
        """
        if anchor is None:
            anchor = (self.first_live, self.seq.num_snapshots - 1)
        return self.delta_stack([(anchor, w) for w in windows],
                                num_lanes=num_lanes)
