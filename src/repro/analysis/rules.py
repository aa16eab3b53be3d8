"""Rules G001–G005, G007–G010: the launch/cache/sync/seeding invariants.

Each rule encodes one contract the executors' module docstrings state in
prose (core/trigrid.py, core/snapshots.py, core/window.py, core/service.py,
core/ingest.py, graph/semiring.py, graph/stability.py) — see docs/ANALYSIS.md for the
catalog with real before/after examples. Rules are static and name-based: they resolve
callees by their rightmost name within one module (no cross-module import
resolution), which is exactly the granularity the contracts are written
at. Escape hatch for a deliberate exception:
``# graphlint: disable=GNNN`` on the offending line.
"""

from __future__ import annotations

import ast
from typing import Iterator

from repro.analysis.linter import (
    Finding,
    Module,
    Rule,
    call_name,
    calls_named,
    defined_function_names,
    get_keyword,
    register,
)


@register
class PallasKernelLocation(Rule):
    """G001: ``pl.pallas_call`` only inside ``repro/kernels/`` modules."""

    id = "G001"
    title = "pallas_call outside a kernels/ module"
    contract = (
        "Every pl.pallas_call lives under src/repro/kernels/*: kernels ship "
        "as <name>.py (pallas_call + BlockSpec), ops.py (jit wrapper) and "
        "ref.py (jnp oracle) with interpret-mode tests, so an ad-hoc "
        "pallas_call in an executor bypasses the platform-derived "
        "interpret default and the oracle test pattern."
    )

    def check(self, module: Module) -> Iterator[Finding]:
        if "kernels" in module.path.parts:
            return
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call) and call_name(node) == "pallas_call":
                yield self.finding(
                    module, node,
                    "pl.pallas_call outside src/repro/kernels/ — add a "
                    "kernel module (with ops.py wrapper + ref.py oracle) "
                    "instead of an inline kernel")
            elif isinstance(node, ast.ImportFrom):
                for alias in node.names:
                    if alias.name == "pallas_call":
                        yield self.finding(
                            module, node,
                            "importing pallas_call outside src/repro/"
                            "kernels/ — kernels own the pallas surface")


@register
class LaneBucketDiscipline(Rule):
    """G002: batched launches must use ``lane_bucket``-derived lane counts."""

    id = "G002"
    title = "batched launch without lane_bucket-derived lane count"
    contract = (
        "The shape-bucketing invariant (core/trigrid.py PR 3): every "
        "stacked lane buffer pads its lane axis to lane_bucket(lanes, "
        "data_extent) — pow2 and mesh-divisible, trailing lanes masked — "
        "so jit trace keys stay (pow2 lanes, pow2 width) and every launch "
        "shards. Raw-integer or un-bucketed num_lanes= arguments, and "
        "batched-engine launches from functions that never compute a "
        "bucket, break that invariant silently."
    )

    #: Stacking entry points whose ``num_lanes=`` must be bucket-derived.
    STACKERS = ("stack_delta_blocks", "delta_stack", "slide_stack")
    #: Batched-engine launches: the enclosing scope must compute a bucket.
    LAUNCHES = ("incremental_additions_batched", "batched_incremental")
    BUCKET_FN = "lane_bucket"

    def check(self, module: Module) -> Iterator[Finding]:
        local_defs = defined_function_names(module.tree)
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if name in self.STACKERS:
                yield from self._check_stacker(module, node, name)
            elif name in self.LAUNCHES and name not in local_defs \
                    and not self._scope_calls_bucket(module, node):
                # Launch calls inside the defining module are engine
                # plumbing (incremental_additions_batched ->
                # batched_incremental), hence the local_defs exemption.
                yield self.finding(
                    module, node,
                    f"{name} launched from a scope that never calls "
                    f"{self.BUCKET_FN}() — pad the lane axis to "
                    "lane_bucket(lanes, data_extent) (masked trailing "
                    "lanes) before launching")

    def _check_stacker(self, module: Module, node: ast.Call,
                       name: str) -> Iterator[Finding]:
        value = get_keyword(node, "num_lanes")
        if value is None:
            yield self.finding(
                module, node,
                f"{name} without num_lanes= stacks the exact lane count — "
                "pass num_lanes=lane_bucket(lanes, data_extent) so the "
                "lane axis is pow2 and mesh-divisible")
            return
        if isinstance(value, ast.Constant):
            what = ("num_lanes=None disables"
                    if value.value is None else
                    f"raw literal num_lanes={value.value!r} bypasses")
            yield self.finding(
                module, node,
                f"{name}: {what} lane bucketing — derive the count via "
                "lane_bucket(lanes, data_extent)")
            return
        if not self._bucket_derived(module, node, value):
            yield self.finding(
                module, node,
                f"{name}: num_lanes is not derived from "
                f"{self.BUCKET_FN}() in the enclosing scope — un-bucketed "
                "lane counts fork jit traces and break mesh divisibility")

    def _bucket_derived(self, module: Module, call: ast.Call,
                        value: ast.expr) -> bool:
        if isinstance(value, ast.Call) and call_name(value) == self.BUCKET_FN:
            return True
        if not isinstance(value, ast.Name):
            return False
        scope = self._outermost_scope(module, call)
        for fn in module.function_ancestors(call):
            # Pass-through wrappers: forwarding a parameter literally named
            # num_lanes (SnapshotStore.delta_stack/slide_stack) is the
            # caller's obligation, not the wrapper's.
            args = fn.args
            params = [a.arg for a in (*args.posonlyargs, *args.args,
                                      *args.kwonlyargs)]
            if value.id == "num_lanes" and value.id in params:
                return True
        return any(
            isinstance(assign, ast.Assign)
            and isinstance(assign.value, ast.Call)
            and call_name(assign.value) == self.BUCKET_FN
            and any(isinstance(t, ast.Name) and t.id == value.id
                    for t in assign.targets)
            for assign in ast.walk(scope))

    def _scope_calls_bucket(self, module: Module, node: ast.Call) -> bool:
        return any(calls_named(self._outermost_scope(module, node),
                               self.BUCKET_FN))

    @staticmethod
    def _outermost_scope(module: Module, node: ast.AST) -> ast.AST:
        ancestors = module.function_ancestors(node)
        return ancestors[-1] if ancestors else module.tree


@register
class CanonicalCacheTags(Rule):
    """G003: SnapshotStore cache tags only via the canonical tag helpers."""

    id = "G003"
    title = "literal SnapshotStore cache tag outside the canonical helpers"
    contract = (
        "Cache tags are part of the store's pure-cache contract: every "
        "block is a pure function of (seq, tag), delta_stack tags embed "
        "the pow2 lane bucket so trace keys follow bucketed shapes, and "
        "pinning is by tag. All tag tuples are therefore built in ONE "
        "module — core/snapshots.py ('T'/'Ts'/'D'/'DS'/'A'/'AS' families, "
        "plus anchor_tag for pin/unpin callers). A literal or f-string tag "
        "anywhere else can silently alias or miss the canonical entry."
    )

    #: Callable name -> index of its tag argument.
    TAG_ARGS = {"pin": 0, "unpin": 0, "_cache_get": 0, "_cache_put": 0,
                "block_for_keys": 1}
    PRIVATE = ("_cache_get", "_cache_put")

    @staticmethod
    def _is_canonical(module: Module) -> bool:
        return any(isinstance(node, ast.ClassDef)
                   and node.name == "SnapshotStore"
                   for node in module.tree.body)

    @staticmethod
    def _literal_tag(value: ast.expr) -> bool:
        if isinstance(value, ast.JoinedStr):
            return True
        if isinstance(value, ast.Constant) and isinstance(value.value, str):
            return True
        if isinstance(value, ast.Tuple) and value.elts:
            head = value.elts[0]
            return (isinstance(head, ast.JoinedStr)
                    or (isinstance(head, ast.Constant)
                        and isinstance(head.value, str)))
        return False

    def check(self, module: Module) -> Iterator[Finding]:
        if self._is_canonical(module):
            return
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if name not in self.TAG_ARGS:
                continue
            if name in self.PRIVATE:
                yield self.finding(
                    module, node,
                    f"SnapshotStore.{name} is private cache plumbing — go "
                    "through a canonical accessor (window_block/delta_block/"
                    "delta_stack/anchor_state_*) so tags stay bucketed")
                continue
            idx = self.TAG_ARGS[name]
            value = (node.args[idx] if len(node.args) > idx
                     else get_keyword(node, "tag"))
            if value is not None and self._literal_tag(value):
                yield self.finding(
                    module, node,
                    f"literal cache tag passed to {name}() — build tags "
                    "with the canonical helpers in core/snapshots.py "
                    "(e.g. anchor_tag) so family strings and lane-bucket "
                    "components cannot drift")


@register
class HostSyncDiscipline(Rule):
    """G004: no host syncs in jitted/hot code; timing syncs via host_sync."""

    id = "G004"
    title = "host synchronization on the device hot path"
    contract = (
        "block_until_ready()/.item()/np.asarray inside a jitted function "
        "(or anything the relax-sweep hot path calls) either fails at "
        "trace time or — worse — silently forces a host round-trip per "
        "sweep. Outside jit, wall-clock timing syncs are legal but must "
        "route through repro.graph.engine.host_sync() so the ONE "
        "sanctioned sync point is greppable; benchmark modules "
        "(benchmarks/) are allowlisted wholesale."
    )

    SYNC_METHODS = ("block_until_ready", "item")
    NUMPY_NAMES = ("np", "numpy")
    HOST_CONVERTERS = ("asarray", "array")
    SANCTIONED = "host_sync"
    HOT_SEEDS = ("relax_sweep",)
    TIMING_DIRS = ("benchmarks",)

    def check(self, module: Module) -> Iterator[Finding]:
        hot = self._hot_functions(module)
        timing_module = bool(set(self.TIMING_DIRS) & set(module.path.parts))
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            enclosing = module.enclosing_function(node)
            in_hot = enclosing in hot
            if isinstance(func, ast.Attribute) and node.args == [] \
                    and func.attr in self.SYNC_METHODS:
                if in_hot:
                    yield self.finding(
                        module, node,
                        f".{func.attr}() inside a jitted/hot-path function "
                        "— host syncs cannot live under trace; hoist to "
                        "the driver")
                elif func.attr == "block_until_ready" and not timing_module \
                        and not self._inside_sanctioned(module, node):
                    yield self.finding(
                        module, node,
                        "bare .block_until_ready() — route timing syncs "
                        "through repro.graph.engine.host_sync() (the "
                        "sanctioned sync point; benchmarks/ is allowlisted)")
            elif in_hot and isinstance(func, ast.Attribute) \
                    and func.attr in self.HOST_CONVERTERS \
                    and isinstance(func.value, ast.Name) \
                    and func.value.id in self.NUMPY_NAMES:
                yield self.finding(
                    module, node,
                    f"np.{func.attr} inside a jitted/hot-path function "
                    "materializes a traced value on host — keep the hot "
                    "path device-only")

    def _inside_sanctioned(self, module: Module, node: ast.AST) -> bool:
        return any(isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                   and fn.name == self.SANCTIONED
                   for fn in module.function_ancestors(node))

    def _hot_functions(self, module: Module) -> set[ast.AST]:
        """Jit-decorated/jit-wrapped defs + everything they (transitively)
        call or nest, resolved by name within this module."""
        defs: list[ast.AST] = [n for n in ast.walk(module.tree)
                               if isinstance(n, (*self._def_types(),))]
        by_name: dict[str, list[ast.AST]] = {}
        for n in defs:
            if not isinstance(n, ast.Lambda):
                by_name.setdefault(n.name, []).append(n)

        hot: set[ast.AST] = set()
        for n in defs:
            if isinstance(n, ast.Lambda):
                continue
            if n.name in self.HOT_SEEDS or any(
                    self._mentions_jit(d) for d in n.decorator_list):
                hot.add(n)
        # jax.jit(fn) / jax.jit(lambda ...) used as an expression.
        for call in calls_named(module.tree, "jit"):
            for arg in call.args:
                if isinstance(arg, ast.Lambda):
                    hot.add(arg)
                elif isinstance(arg, ast.Name):
                    hot.update(by_name.get(arg.id, ()))

        changed = True
        while changed:
            changed = False
            for fn in list(hot):
                for node in ast.walk(fn):
                    if isinstance(node, (*self._def_types(),)) \
                            and node not in hot:
                        hot.add(node)
                        changed = True
                    elif isinstance(node, ast.Call):
                        for callee in by_name.get(call_name(node) or "", ()):
                            if callee not in hot:
                                hot.add(callee)
                                changed = True
        return hot

    @staticmethod
    def _def_types():
        return (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)

    @staticmethod
    def _mentions_jit(decorator: ast.expr) -> bool:
        return any((isinstance(n, ast.Name) and n.id == "jit")
                   or (isinstance(n, ast.Attribute) and n.attr == "jit")
                   for n in ast.walk(decorator))


@register
class SemiringSurface(Rule):
    """G005: semiring definitions complete + registered in ALL_SEMIRINGS."""

    id = "G005"
    title = "incomplete or unregistered Semiring definition"
    contract = (
        "Every monotone path semiring must supply the full contract "
        "surface (name/reduce/identity/source_value/combine, by keyword; "
        "reduce a literal 'min'/'max' — the engine branches on it "
        "statically) and, in a module that defines the ALL_SEMIRINGS "
        "registry, appear in that registry: executors, benchmarks and the "
        "evolve CLI enumerate ALL_SEMIRINGS, so an unregistered semiring "
        "is silently untested and unservable."
    )

    REQUIRED = ("name", "reduce", "identity", "source_value", "combine")
    REGISTRY = "ALL_SEMIRINGS"

    def check(self, module: Module) -> Iterator[Finding]:
        instances: dict[str, ast.Assign] = {}
        registry_value: "ast.expr | None" = None
        for stmt in module.tree.body:
            if not isinstance(stmt, ast.Assign):
                continue
            targets = [t.id for t in stmt.targets if isinstance(t, ast.Name)]
            if self.REGISTRY in targets:
                registry_value = stmt.value
            elif isinstance(stmt.value, ast.Call) \
                    and call_name(stmt.value) == "Semiring" and targets:
                instances[targets[0]] = stmt
                yield from self._check_call(module, stmt.value)
        # AnnAssign (ALL_SEMIRINGS: dict[...] = {...}) registry form.
        for stmt in module.tree.body:
            if isinstance(stmt, ast.AnnAssign) \
                    and isinstance(stmt.target, ast.Name) \
                    and stmt.target.id == self.REGISTRY:
                registry_value = stmt.value
        if registry_value is not None:
            registered = {n.id for n in ast.walk(registry_value)
                          if isinstance(n, ast.Name)}
            for name, stmt in instances.items():
                if name not in registered:
                    yield self.finding(
                        module, stmt,
                        f"Semiring {name} is not referenced by "
                        f"{self.REGISTRY} — unregistered semirings are "
                        "invisible to executors, benchmarks and the CLI")

    def _check_call(self, module: Module,
                    call: ast.Call) -> Iterator[Finding]:
        if call.args:
            yield self.finding(
                module, call,
                "Semiring(...) with positional arguments — use keywords so "
                "the contract surface is checkable and reorder-proof")
        given = {kw.arg for kw in call.keywords if kw.arg}
        missing = [k for k in self.REQUIRED if k not in given]
        if missing:
            yield self.finding(
                module, call,
                f"Semiring(...) missing required field(s) "
                f"{', '.join(missing)} — the monotone-op contract surface "
                "is name/reduce/identity/source_value/combine")
        reduce_kw = get_keyword(call, "reduce")
        if reduce_kw is not None and not (
                isinstance(reduce_kw, ast.Constant)
                and reduce_kw.value in ("min", "max")):
            yield self.finding(
                module, call,
                'Semiring reduce= must be the literal "min" or "max" — '
                "the engine selects its segment reduction statically")


@register
class ServiceSyncBoundary(Rule):
    """G007: service modules sync only at packed-launch boundaries."""

    id = "G007"
    title = "per-query host sync in a service scheduling loop"
    contract = (
        "The query service's hot loop (admission -> pack -> launch, "
        "core/service.py) must stay sync-free: the ONE host sync per "
        "packed launch lives at the campaign boundary, inside a function "
        "whose name ends with _launch (core/window.py::_slide_launch or a "
        "service-side *_launch executor). A host_sync() / "
        ".block_until_ready() / .item() anywhere else in a service module "
        "— per admitted query, per lane, per client in a scheduling loop "
        "— serializes the open-loop pipeline and destroys batching (it "
        "also makes scheduling wall-clock-dependent, breaking the "
        "machine-independent exact fields BENCH_serve gates on). Applies "
        "to modules named service; other modules keep G004's discipline."
    )

    SYNC_METHODS = ("block_until_ready", "item")
    SANCTIONED_SUFFIX = "_launch"
    MODULE_NAME = "service"

    def check(self, module: Module) -> Iterator[Finding]:
        if module.dotted_name().split(".")[-1] != self.MODULE_NAME:
            return
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            is_sync = (
                (isinstance(func, ast.Name) and func.id == "host_sync")
                or (isinstance(func, ast.Attribute)
                    and func.attr == "host_sync")
                or (isinstance(func, ast.Attribute) and node.args == []
                    and func.attr in self.SYNC_METHODS))
            if is_sync and not self._at_launch_boundary(module, node):
                label = (func.id if isinstance(func, ast.Name)
                         else f".{func.attr}")
                yield self.finding(
                    module, node,
                    f"{label} outside a *{self.SANCTIONED_SUFFIX} function "
                    "— the service hot loop syncs once per packed launch "
                    "at the campaign boundary, never per query")

    def _at_launch_boundary(self, module: Module, node: ast.AST) -> bool:
        return any(isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                   and fn.name.endswith(self.SANCTIONED_SUFFIX)
                   for fn in module.function_ancestors(node))


@register
class StabilitySeedDiscipline(Rule):
    """G008: seed frontiers come from graph/stability.py, not raw Δ sweeps."""

    id = "G008"
    title = "raw relax_sweep seeding outside the stability layer"
    contract = (
        "Frontier seeding is the stable-vertex analysis' monopoly "
        "(graph/stability.py::seed_state): it applies the semiring's "
        "monotone-improvement test so stable vertices never enter the seed "
        "frontier, and it is the one place the instability/delta mode "
        "switch and stable_fraction accounting live. A direct relax_sweep "
        "call anywhere else re-derives a seed frontier from the raw Δ edge "
        "endpoint set — bypassing the pruning, the mode switch and the "
        "accounting at once. Only the stability module itself and the "
        "engine's fixpoint machinery (_fixpoint, where relax_sweep is the "
        "per-sweep step, not a seeding, and relax_sweep_fused, whose "
        "reference path iterates relax_sweep inside one fused chunk) may "
        "call it."
    )

    SWEEP = "relax_sweep"
    STABILITY_MODULE = "repro.graph.stability"
    ENGINE_MODULE = "repro.graph.engine"
    ENGINE_SANCTIONED = ("_fixpoint", "relax_sweep_fused")

    def check(self, module: Module) -> Iterator[Finding]:
        dotted = module.dotted_name()
        if dotted == self.STABILITY_MODULE:
            return
        for node in calls_named(module.tree, self.SWEEP):
            if dotted == self.ENGINE_MODULE and any(
                    isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and fn.name in self.ENGINE_SANCTIONED
                    for fn in module.function_ancestors(node)):
                continue
            yield self.finding(
                module, node,
                f"{self.SWEEP} called outside graph/stability.py — seed "
                "frontiers must come from repro.graph.stability.seed_state "
                "(the stable-vertex analysis), not a raw Δ edge sweep")


@register
class IngestCutDiscipline(Rule):
    """G009: snapshots are cut only via Watermark.cut; no ad-hoc store writes."""

    id = "G009"
    title = "snapshot write outside the watermark cut path"
    contract = (
        "A live SnapshotStore grows through exactly one write path: "
        "ingest.Watermark.cut consumes watermarked events (timestamp "
        "order, last-op-wins, redundancy filtered), maintains the running "
        "common graph, and installs the snapshot + canonical Δ pair via "
        "SnapshotStore.ingest_cut. An ingest_cut call anywhere else skips "
        "that bookkeeping (metrics, sealing, common-graph maintenance); "
        "growing the live sequence directly (.snapshot_keys/.additions/"
        ".deletions .append) desynchronizes the store's window cache from "
        "its sequence; and writing the store's _t/_deltas/_blocks caches from "
        "outside core/snapshots.py plants entries the pure-cache contract "
        "cannot rebuild. All three are flagged outside their one legal "
        "home (ingest.Watermark.cut / ingest.LiveSequence.append / the "
        "SnapshotStore module itself)."
    )

    WRITE_PATH = "ingest_cut"
    INGEST_MODULE = "repro.core.ingest"
    SANCTIONED_FN = "cut"
    GROW_ATTRS = ("snapshot_keys", "additions", "deletions")
    CACHE_ATTRS = ("_t", "_deltas", "_blocks")

    def check(self, module: Module) -> Iterator[Finding]:
        dotted = module.dotted_name()
        in_ingest = dotted == self.INGEST_MODULE
        canonical = any(isinstance(node, ast.ClassDef)
                        and node.name == "SnapshotStore"
                        for node in module.tree.body)
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call) \
                    and call_name(node) == self.WRITE_PATH:
                if not (in_ingest and self._in_cut(module, node)):
                    yield self.finding(
                        module, node,
                        f"{self.WRITE_PATH} called outside "
                        "ingest.Watermark.cut — snapshots are born only "
                        "from watermarked cuts (event ordering, sealing, "
                        "common-graph maintenance live there)")
            elif isinstance(node, ast.Call) and not in_ingest \
                    and self._grows_sequence(node):
                yield self.finding(
                    module, node,
                    "appending to a live sequence's snapshot_keys/"
                    "additions/deletions outside core/ingest.py — the "
                    "store's window cache would not see the new snapshot; "
                    "cut it via ingest.Watermark.cut")
            elif isinstance(node, ast.Assign) and not canonical:
                for target in node.targets:
                    attr = self._cache_subscript(target)
                    if attr is not None:
                        yield self.finding(
                            module, node,
                            f"direct write to SnapshotStore.{attr}[...] "
                            "outside core/snapshots.py — cache entries "
                            "must be installable only by the store (pure-"
                            "cache contract); use ingest_cut/the canonical "
                            "accessors")

    def _in_cut(self, module: Module, node: ast.AST) -> bool:
        return any(isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                   and fn.name == self.SANCTIONED_FN
                   for fn in module.function_ancestors(node))

    def _grows_sequence(self, node: ast.Call) -> bool:
        func = node.func
        return (isinstance(func, ast.Attribute) and func.attr == "append"
                and isinstance(func.value, ast.Attribute)
                and func.value.attr in self.GROW_ATTRS)

    def _cache_subscript(self, target: ast.expr) -> "str | None":
        if isinstance(target, ast.Subscript) \
                and isinstance(target.value, ast.Attribute) \
                and target.value.attr in self.CACHE_ATTRS:
            return target.value.attr
        return None


@register
class FusedLaunchDiscipline(Rule):
    """G010: fused relax chunks launch only through the engine's fixpoint."""

    id = "G010"
    title = "fused relax chunk launched outside the sanctioned fixpoint path"
    contract = (
        "relax_sweep_fused (the fused multi-sweep chunk over "
        "kernels/edge_relax_multi) extends G008's seeding monopoly: it IS "
        "a relax-sweep sequence, so launching it from an executor re-opens "
        "the raw-Δ seeding hole G008 closed, and it additionally carries "
        "the bit-exactness contract (fused(k) == k relax_sweep "
        "applications) that only the engine's chunked fixpoint "
        "(engine._fixpoint) and the stability layer's seed sweep "
        "(graph/stability.py, k=1) are tested to preserve. Everything "
        "else reaches fused execution through the fused_k LAUNCH OPTION "
        "threaded engine -> trigrid -> window -> service — and that knob "
        "must flow from launch options (a variable or attribute), never a "
        "literal at a call site, so one configuration point controls every "
        "launch in a run and packed lanes cannot silently mix chunk sizes."
    )

    FUSED = "relax_sweep_fused"
    KNOB = "fused_k"
    STABILITY_MODULE = "repro.graph.stability"
    ENGINE_MODULE = "repro.graph.engine"
    ENGINE_SANCTIONED = "_fixpoint"

    def check(self, module: Module) -> Iterator[Finding]:
        dotted = module.dotted_name()
        if dotted != self.STABILITY_MODULE:
            for node in calls_named(module.tree, self.FUSED):
                if dotted == self.ENGINE_MODULE and any(
                        isinstance(fn, (ast.FunctionDef,
                                        ast.AsyncFunctionDef))
                        and fn.name == self.ENGINE_SANCTIONED
                        for fn in module.function_ancestors(node)):
                    continue
                yield self.finding(
                    module, node,
                    f"{self.FUSED} called outside graph/stability.py and "
                    "engine._fixpoint — executors reach fused execution "
                    "via the fused_k launch option (run_to_fixpoint/"
                    "incremental_additions/...), never by launching fused "
                    "chunks directly")
        if dotted == self.ENGINE_MODULE:
            return
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            value = get_keyword(node, self.KNOB)
            if isinstance(value, ast.Constant):
                yield self.finding(
                    module, node,
                    f"literal {self.KNOB}={value.value!r} at a call site — "
                    "the fused chunk size is a launch option: thread it "
                    "from the caller's options (a variable or attribute), "
                    "so one knob configures every launch in the run")
