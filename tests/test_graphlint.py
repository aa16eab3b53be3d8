"""graphlint fixture corpus + self-check (src/repro/analysis).

Each rule gets a paired bad/good fixture: the bad snippet must trigger
exactly its rule (no cross-rule noise), the good snippet must be clean
under ALL rules. Fixtures are written into a tmp mini-repo (pyproject
marker + src/repro layout + docs/API.md) so root detection, dotted-name
derivation and the G006 doc lookup run exactly as they do on the real
tree — which the self-check at the bottom then asserts is clean.
"""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys

import pytest

from repro.analysis import Linter, all_rules, get_rule
from repro.analysis.linter import Module, render_json

REPO = pathlib.Path(__file__).resolve().parent.parent

API_DOC = """# API reference

## `repro.core.documented`

### `covered(x)`
Documented and docstringed.
"""


def make_repo(tmp_path: pathlib.Path) -> pathlib.Path:
    """A minimal rooted repo skeleton fixtures are dropped into."""
    (tmp_path / "pyproject.toml").write_text("[project]\nname='fix'\n")
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "API.md").write_text(API_DOC)
    (tmp_path / "src" / "repro").mkdir(parents=True)
    return tmp_path


def lint_snippet(tmp_path, code, relpath="src/repro/mod.py", rules=None):
    root = make_repo(tmp_path)
    target = root / relpath
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(code)
    linter = Linter(rules=rules, root=root)
    return linter.lint([target])


def assert_only_rule(findings, rule_id, count=None):
    """The bad fixture discipline: found, and nothing but this rule."""
    assert findings, f"expected {rule_id} findings, got none"
    assert {f.rule for f in findings} == {rule_id}, findings
    if count is not None:
        assert len(findings) == count, findings


# -- G001: pallas_call location ----------------------------------------------

BAD_G001 = """\
import jax.experimental.pallas as pl

def sneaky(x):
    return pl.pallas_call(lambda ref: ref, out_shape=x)(x)
"""

GOOD_G001 = BAD_G001  # same code is legal inside kernels/


def test_g001_bad(tmp_path):
    findings = lint_snippet(tmp_path, BAD_G001,
                            relpath="src/repro/core/sneaky.py")
    assert_only_rule(findings, "G001", count=1)


def test_g001_good_inside_kernels(tmp_path):
    assert lint_snippet(tmp_path, GOOD_G001,
                        relpath="src/repro/kernels/fine.py") == []


def test_g001_flags_import_too(tmp_path):
    code = "from jax.experimental.pallas import pallas_call\n"
    findings = lint_snippet(tmp_path, code,
                            relpath="src/repro/launch/bad_import.py")
    assert_only_rule(findings, "G001", count=1)


# -- G002: lane_bucket discipline --------------------------------------------

BAD_G002 = """\
from repro.graph.edgeset import stack_delta_blocks

def stack_raw(lanes, n):
    return stack_delta_blocks(lanes, n, num_lanes=7)

def stack_unbucketed(lanes, n):
    k = len(lanes)
    return stack_delta_blocks(lanes, n, num_lanes=k)

def launch_unbucketed(view, state, stacked):
    from repro.graph.engine import incremental_additions_batched
    return incremental_additions_batched(view, state, stacked)
"""

GOOD_G002 = """\
from repro.graph.edgeset import lane_bucket, stack_delta_blocks
from repro.graph.engine import incremental_additions_batched

def stack_bucketed(lanes, n, extent):
    bucket = lane_bucket(len(lanes), extent)
    return stack_delta_blocks(lanes, n, num_lanes=bucket)

def stack_inline(lanes, n, extent):
    return stack_delta_blocks(lanes, n,
                              num_lanes=lane_bucket(len(lanes), extent))

def forwarding_wrapper(lanes, n, num_lanes=None):
    # pass-through: the caller owns the bucketing obligation
    return stack_delta_blocks(lanes, n, num_lanes=num_lanes)

def launch_bucketed(view, state, lanes, extent):
    bucket = lane_bucket(len(lanes), extent)
    def inner(stacked):
        return incremental_additions_batched(view, state, stacked)
    return inner, bucket
"""


def test_g002_bad(tmp_path):
    findings = lint_snippet(tmp_path, BAD_G002)
    assert_only_rule(findings, "G002", count=3)


def test_g002_good(tmp_path):
    assert lint_snippet(tmp_path, GOOD_G002) == []


def test_g002_missing_num_lanes(tmp_path):
    code = ("from repro.graph.edgeset import stack_delta_blocks\n"
            "def f(lanes, n):\n"
            "    return stack_delta_blocks(lanes, n)\n")
    findings = lint_snippet(tmp_path, code)
    assert_only_rule(findings, "G002", count=1)
    assert "without num_lanes" in findings[0].message


# -- G003: canonical cache tags ----------------------------------------------

BAD_G003 = """\
def hold(store, qkey, link):
    store.pin(("AS", qkey, link))

def peek(store, key):
    return store._cache_get(("T", 0, 3))
"""

GOOD_G003 = """\
from repro.core.snapshots import anchor_tag

def hold(store, qkey, link):
    store.pin(anchor_tag(qkey, link))

def stacked(store, hops, num_lanes):
    return store.delta_stack(hops, num_lanes=num_lanes)
"""


def test_g003_bad(tmp_path):
    findings = lint_snippet(tmp_path, BAD_G003)
    assert_only_rule(findings, "G003", count=2)


def test_g003_good(tmp_path):
    assert lint_snippet(tmp_path, GOOD_G003) == []


def test_g003_exempts_canonical_module(tmp_path):
    code = ("class SnapshotStore:\n"
            "    '''The canonical tag module.'''\n"
            "    def anchor_state_get(self, qkey, window):\n"
            "        '''doc'''\n"
            "        return self._cache_get(('AS', qkey, tuple(window)))\n")
    assert lint_snippet(tmp_path, code,
                        rules=[get_rule("G003")]) == []


# -- G004: host-sync discipline ----------------------------------------------

BAD_G004_JIT = """\
import functools
import jax
import numpy as np

@functools.partial(jax.jit, static_argnums=(0,))
def hot(n, values):
    host = np.asarray(values)
    values.block_until_ready()
    return values
"""

BAD_G004_BARE = """\
def time_things(values):
    values.block_until_ready()
    return values
"""

GOOD_G004 = """\
import jax
import numpy as np
from repro.graph.engine import host_sync

def relax_sweep(view, values):
    return values + 1

def timed_driver(values):
    host_sync(values)
    return values

def host_side_report(result):
    # not reachable from any jitted function: np.asarray is fine
    return np.asarray(result)
"""


def test_g004_inside_jit(tmp_path):
    findings = lint_snippet(tmp_path, BAD_G004_JIT)
    assert_only_rule(findings, "G004", count=2)
    assert any("jitted" in f.message for f in findings)


def test_g004_bare_sync(tmp_path):
    findings = lint_snippet(tmp_path, BAD_G004_BARE)
    assert_only_rule(findings, "G004", count=1)
    assert "host_sync" in findings[0].message


def test_g004_good(tmp_path):
    assert lint_snippet(tmp_path, GOOD_G004) == []


def test_g004_benchmarks_allowlisted(tmp_path):
    assert lint_snippet(tmp_path, BAD_G004_BARE,
                        relpath="benchmarks/bench_thing.py") == []


def test_g004_hot_path_closure(tmp_path):
    # relax_sweep -> helper chain: a sync two calls away is still hot.
    code = ("def helper(values):\n"
            "    return values.item()\n"
            "def middle(values):\n"
            "    return helper(values)\n"
            "def relax_sweep(view, values):\n"
            "    return middle(values)\n")
    findings = lint_snippet(tmp_path, code)
    assert_only_rule(findings, "G004", count=1)


def test_g004_jit_wrapped_lambda(tmp_path):
    code = ("import jax\n"
            "import numpy as np\n"
            "def make(cfg):\n"
            "    return jax.jit(lambda v: np.asarray(v))\n")
    findings = lint_snippet(tmp_path, code)
    assert_only_rule(findings, "G004", count=1)


# -- G005: semiring contract surface -----------------------------------------

BAD_G005 = """\
from repro.graph.semiring import Semiring

GOOD = Semiring(name="bfs", reduce="min", identity=1.0,
                source_value=0.0, combine="add")
PARTIAL = Semiring(name="oops", reduce="min")
SOFTMIN = Semiring(name="soft", reduce="softmin", identity=0.0,
                   source_value=0.0, combine="add")

ALL_SEMIRINGS = {s.name: s for s in (GOOD, PARTIAL)}
"""

GOOD_G005 = """\
from repro.graph.semiring import Semiring

BFS = Semiring(name="bfs", reduce="min", identity=1.0,
               source_value=0.0, combine="add_unit")
SSSP = Semiring(name="sssp", reduce="min", identity=1.0,
                source_value=0.0, combine="add")
SSWP = Semiring(name="sswp", reduce="max", identity=0.0,
                source_value=1.0, combine="min")
SSNP = Semiring(name="ssnp", reduce="min", identity=1.0,
                source_value=0.0, combine="max")
VITERBI = Semiring(name="viterbi", reduce="max", identity=0.0,
                   source_value=1.0, combine="mul")

ALL_SEMIRINGS = {s.name: s for s in (BFS, SSSP, SSWP, SSNP, VITERBI)}
"""


def test_g005_bad(tmp_path):
    findings = lint_snippet(tmp_path, BAD_G005)
    # PARTIAL misses fields, SOFTMIN has a non-literal-min/max reduce AND
    # is unregistered — three findings, all G005.
    assert_only_rule(findings, "G005", count=3)
    messages = " | ".join(f.message for f in findings)
    assert "missing required field" in messages
    assert '"min" or "max"' in messages
    assert "ALL_SEMIRINGS" in messages


def test_g005_good(tmp_path):
    assert lint_snippet(tmp_path, GOOD_G005) == []


# -- G006: API.md coverage + docstrings --------------------------------------

BAD_G006 = """\
def covered(x):
    return x

def newcomer(x):
    '''Docstringed but absent from API.md.'''
    return x
"""

GOOD_G006 = """\
def covered(x):
    '''Documented and docstringed.'''
    return x

def _helper(x):
    return x
"""


def test_g006_bad(tmp_path):
    findings = lint_snippet(tmp_path, BAD_G006,
                            relpath="src/repro/core/documented.py")
    # covered() lacks a docstring; newcomer() lacks an API.md entry.
    assert_only_rule(findings, "G006", count=2)
    messages = " | ".join(f.message for f in findings)
    assert "no docstring" in messages
    assert "undocumented" in messages


def test_g006_good(tmp_path):
    assert lint_snippet(tmp_path, GOOD_G006,
                        relpath="src/repro/core/documented.py") == []


def test_g006_stale_entry_flagged_in_api_md(tmp_path):
    # Module exists but no longer defines covered(): the stale entry is
    # reported against docs/API.md, not the source file.
    findings = lint_snippet(tmp_path, "def other(x):\n    '''doc'''\n",
                            relpath="src/repro/core/documented.py")
    g006 = [f for f in findings if f.rule == "G006"]
    stale = [f for f in g006 if "stale" in f.message]
    assert stale and stale[0].path == "docs/API.md"


def test_g006_out_of_scope_module_skipped(tmp_path):
    # No API.md section for repro.mod: the docstring gate does not apply.
    assert lint_snippet(tmp_path, "def undocumented(x):\n    return x\n",
                        rules=[get_rule("G006")]) == []


# -- G007: service sync boundary ---------------------------------------------

BAD_G007 = """\
from repro.graph.engine import host_sync

def schedule_turn(service, pending):
    for query in pending:
        res = service.launch_one(query)
        host_sync(res.values)
        service.latencies.append(res.wall)
    return service

def account(results):
    return [r.edge_work.item() for r in results]
"""

GOOD_G007 = """\
from repro.graph.engine import host_sync

def _packed_launch(store, windows, states):
    '''One batched launch; the campaign-boundary sync lives here.'''
    res = store.run(windows, states)
    host_sync(res.values)
    return res

def schedule_turn(service, launches):
    return [_packed_launch(service.store, w, s) for (w, s) in launches]
"""


def test_g007_bad(tmp_path):
    # a per-query host_sync in the scheduling loop + a per-result .item()
    findings = lint_snippet(tmp_path, BAD_G007,
                            relpath="src/repro/core/service.py")
    assert_only_rule(findings, "G007", count=2)
    assert all("_launch" in f.message for f in findings)


def test_g007_good(tmp_path):
    assert lint_snippet(tmp_path, GOOD_G007,
                        relpath="src/repro/core/service.py") == []


def test_g007_scoped_to_service_modules(tmp_path):
    # same code elsewhere answers to G004's discipline, not G007's
    assert lint_snippet(tmp_path, BAD_G007,
                        relpath="src/repro/core/scheduler.py",
                        rules=[get_rule("G007")]) == []


def test_g007_method_call_form_flagged(tmp_path):
    code = ("def poll(engine, res):\n"
            "    engine.host_sync(res.values)\n")
    findings = lint_snippet(tmp_path, code,
                            relpath="src/repro/launch/service.py")
    assert_only_rule(findings, "G007", count=1)


# -- G008: stability-layer seeding discipline --------------------------------

BAD_G008 = """\
from repro.graph.engine import relax_sweep

def seed_from_raw_delta(semiring, n, values, parent, delta_blocks):
    frontier = values == values  # all-on: the raw Delta endpoint seeding
    return relax_sweep(semiring, n, values, parent, frontier, delta_blocks)
"""

GOOD_G008 = """\
from repro.graph.stability import seed_state

def seed_properly(semiring, n, values, parent, delta_blocks):
    return seed_state(semiring, n, values, parent, delta_blocks)
"""


def test_g008_bad(tmp_path):
    findings = lint_snippet(tmp_path, BAD_G008,
                            relpath="src/repro/core/executor.py")
    assert_only_rule(findings, "G008", count=1)
    assert "seed_state" in findings[0].message


def test_g008_good(tmp_path):
    assert lint_snippet(tmp_path, GOOD_G008,
                        relpath="src/repro/core/executor.py") == []


def test_g008_stability_module_exempt(tmp_path):
    # the analysis itself owns the one sanctioned seeding call site
    assert lint_snippet(tmp_path, BAD_G008,
                        relpath="src/repro/graph/stability.py",
                        rules=[get_rule("G008")]) == []


def test_g008_engine_fixpoint_exempt(tmp_path):
    # _fixpoint's per-sweep relax_sweep is iteration, not seeding — but a
    # relax_sweep anywhere else in the engine module is still flagged.
    code = ("def relax_sweep(semiring, n, values, parent, frontier, blocks):\n"
            "    '''the sweep primitive itself'''\n"
            "    return values\n"
            "def _fixpoint(semiring, n, values, parent, frontier, blocks):\n"
            "    def body(carry):\n"
            "        return relax_sweep(semiring, n, *carry, blocks)\n"
            "    return body\n"
            "def rogue_seed(semiring, n, values, parent, frontier, blocks):\n"
            "    return relax_sweep(semiring, n, values, parent, frontier,\n"
            "                       blocks)\n")
    findings = lint_snippet(tmp_path, code,
                            relpath="src/repro/graph/engine.py",
                            rules=[get_rule("G008")])
    assert_only_rule(findings, "G008", count=1)
    assert findings[0].line > 7  # only the rogue call, not _fixpoint's


# -- G009: watermark cut discipline ------------------------------------------

BAD_G009 = """\
import numpy as np

def sneak_snapshot(store, keys):
    store.ingest_cut(keys, np.empty(0, np.int64), np.empty(0, np.int64))

def grow_directly(store, keys):
    store.seq.snapshot_keys.append(keys)

def plant_cache_entry(store, i, j, keys):
    store._t[(i, j)] = keys
"""

GOOD_G009 = """\
def serve_live(watermark, ts):
    watermark.advance(ts)
    return watermark.cut()

def retire_old(watermark):
    return watermark.compact()
"""


def test_g009_bad(tmp_path):
    # an ad-hoc ingest_cut, a direct sequence append, a planted cache entry
    findings = lint_snippet(tmp_path, BAD_G009,
                            relpath="src/repro/launch/firehose.py")
    assert_only_rule(findings, "G009", count=3)
    messages = " | ".join(f.message for f in findings)
    assert "Watermark.cut" in messages
    assert "window cache" in messages
    assert "pure-cache" in messages


def test_g009_good(tmp_path):
    assert lint_snippet(tmp_path, GOOD_G009,
                        relpath="src/repro/launch/firehose.py") == []


def test_g009_ingest_cut_exempt_only_inside_cut(tmp_path):
    # In core/ingest.py: legal from a function named cut, flagged elsewhere.
    code = ("import numpy as np\n"
            "class Watermark:\n"
            "    '''doc'''\n"
            "    def cut(self):\n"
            "        '''doc'''\n"
            "        return self.store.ingest_cut(self.k, self.a, self.d)\n"
            "    def shortcut(self):\n"
            "        '''doc'''\n"
            "        return self.store.ingest_cut(self.k, self.a, self.d)\n")
    findings = lint_snippet(tmp_path, code,
                            relpath="src/repro/core/ingest.py",
                            rules=[get_rule("G009")])
    assert_only_rule(findings, "G009", count=1)
    assert findings[0].line > 6  # only shortcut's call, not cut's


def test_g009_canonical_module_exempt_for_cache_writes(tmp_path):
    code = ("class SnapshotStore:\n"
            "    '''the canonical store module'''\n"
            "    def ingest_cut(self, keys, added, deleted):\n"
            "        '''doc'''\n"
            "        self._t[(0, 0)] = keys\n"
            "        return 0\n")
    assert lint_snippet(tmp_path, code,
                        relpath="src/repro/core/snapshots.py",
                        rules=[get_rule("G009")]) == []


def test_g009_flags_a_planted_delta_memo_entry(tmp_path):
    code = ("def plant_delta(store, parent, child, keys):\n"
            "    store._deltas[(parent, child)] = keys\n")
    findings = lint_snippet(tmp_path, code,
                            relpath="src/repro/launch/firehose.py",
                            rules=[get_rule("G009")])
    assert_only_rule(findings, "G009", count=1)
    assert "SnapshotStore._deltas" in findings[0].message


# -- G010: fused-launch discipline --------------------------------------------

BAD_G010 = """\
from repro.graph.engine import relax_sweep_fused, run_to_fixpoint

def hand_rolled_chunk(semiring, n, values, parent, frontier, blocks):
    return relax_sweep_fused(semiring, n, values, parent, frontier, blocks,
                             k=4)

def hardcoded_knob(view, semiring, source):
    return run_to_fixpoint(view, semiring, source, fused_k=8)
"""

GOOD_G010 = """\
from repro.graph.engine import run_to_fixpoint

def launch(view, semiring, source, options):
    return run_to_fixpoint(view, semiring, source,
                           fused_k=options.fused_k)

def launch_threaded(view, semiring, source, fused_k):
    return run_to_fixpoint(view, semiring, source, fused_k=fused_k)
"""


def test_g010_bad(tmp_path):
    # a direct fused-chunk launch + a literal fused_k at a call site
    findings = lint_snippet(tmp_path, BAD_G010,
                            relpath="src/repro/core/executor.py")
    assert_only_rule(findings, "G010", count=2)
    messages = " | ".join(f.message for f in findings)
    assert "launch option" in messages
    assert "fused_k=8" in messages


def test_g010_good(tmp_path):
    assert lint_snippet(tmp_path, GOOD_G010,
                        relpath="src/repro/core/executor.py") == []


def test_g010_stability_module_may_call_fused(tmp_path):
    # the seed sweep (k=1 fused chunk) is stability's sanctioned call —
    # but k= is not the fused_k knob, so only the call-site grant matters
    code = ("from repro.graph.engine import relax_sweep_fused\n"
            "def seed_state(semiring, n, values, parent, frontier, blocks):\n"
            "    return relax_sweep_fused(semiring, n, values, parent,\n"
            "                             frontier, blocks, k=1)\n")
    assert lint_snippet(tmp_path, code,
                        relpath="src/repro/graph/stability.py",
                        rules=[get_rule("G010")]) == []


def test_g010_engine_fixpoint_exempt(tmp_path):
    # _fixpoint's chunked body consumes fused chunks; a fused launch
    # anywhere else in the engine module is still flagged.
    code = ("def relax_sweep_fused(semiring, n, values, parent, frontier,\n"
            "                      blocks, k=1):\n"
            "    '''the fused chunk primitive itself'''\n"
            "    return values\n"
            "def _fixpoint(semiring, n, values, parent, frontier, blocks,\n"
            "              fused_k=1):\n"
            "    def chunk(carry):\n"
            "        return relax_sweep_fused(semiring, n, *carry, blocks,\n"
            "                                 k=fused_k)\n"
            "    return chunk\n"
            "def rogue(semiring, n, values, parent, frontier, blocks):\n"
            "    return relax_sweep_fused(semiring, n, values, parent,\n"
            "                             frontier, blocks, k=2)\n")
    findings = lint_snippet(tmp_path, code,
                            relpath="src/repro/graph/engine.py",
                            rules=[get_rule("G010")])
    assert_only_rule(findings, "G010", count=1)
    assert findings[0].line > 10  # only the rogue launch, not _fixpoint's


def test_g010_engine_module_may_default_the_knob(tmp_path):
    # engine plumbing forwards fused_k between its own entry points; the
    # literal-knob check applies outside the engine module only.
    code = ("def run_to_fixpoint(view, semiring, source, fused_k=1):\n"
            "    '''doc'''\n"
            "    return _fixpoint_jit(view, semiring, source, fused_k=1)\n")
    assert lint_snippet(tmp_path, code,
                        relpath="src/repro/graph/engine.py",
                        rules=[get_rule("G010")]) == []


# -- suppressions, engine plumbing, CLI --------------------------------------

def test_line_suppression(tmp_path):
    code = BAD_G004_BARE.replace(
        "values.block_until_ready()",
        "values.block_until_ready()  # graphlint: disable=G004")
    assert lint_snippet(tmp_path, code) == []


def test_file_suppression(tmp_path):
    code = "# graphlint: disable-file=G004\n" + BAD_G004_BARE
    assert lint_snippet(tmp_path, code) == []


def test_suppression_is_per_rule(tmp_path):
    code = BAD_G004_BARE.replace(
        "values.block_until_ready()",
        "values.block_until_ready()  # graphlint: disable=G001")
    findings = lint_snippet(tmp_path, code)
    assert_only_rule(findings, "G004", count=1)


def test_rule_registry_complete():
    assert [r.id for r in all_rules()] == \
        ["G001", "G002", "G003", "G004", "G005", "G006", "G007", "G008",
         "G009", "G010"]
    for rule in all_rules():
        assert rule.title and rule.contract
    with pytest.raises(KeyError):
        get_rule("G999")


def test_module_dotted_name(tmp_path):
    root = make_repo(tmp_path)
    path = root / "src" / "repro" / "core" / "thing.py"
    path.parent.mkdir(parents=True, exist_ok=True)
    m = Module(path, "x = 1\n", root)
    assert m.dotted_name() == "repro.core.thing"
    assert m.rel == "src/repro/core/thing.py"


def test_render_json_shape(tmp_path):
    findings = lint_snippet(tmp_path, BAD_G004_BARE)
    payload = json.loads(render_json(findings, files_checked=1))
    assert payload["version"] == 1
    assert payload["count"] == 1
    assert payload["findings"][0]["rule"] == "G004"
    assert set(payload["findings"][0]) == \
        {"rule", "path", "line", "col", "message"}


def test_cli_json_exit_codes(tmp_path):
    root = make_repo(tmp_path)
    bad = root / "src" / "repro" / "bad.py"
    bad.write_text(BAD_G004_BARE)
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "invariant_lint.py"),
         "--format", "json", str(bad)],
        capture_output=True, text=True)
    assert proc.returncode == 1, proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["count"] == 1 and payload["findings"][0]["rule"] == "G004"

    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "invariant_lint.py"),
         "--select", "G001", str(bad)],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- the gate itself: the real tree is clean ---------------------------------

def test_graphlint_clean_on_real_src():
    linter = Linter(root=REPO)
    findings = linter.lint([REPO / "src"])
    assert findings == [], "\n".join(f.render() for f in findings)
    assert linter.files_checked > 50
