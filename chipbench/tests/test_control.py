"""The comparison that decides ``correct`` fails its control and each fault.

The control is the plain reference computed in bfloat16, one precision
below the float32 the configurations state. The faults break the timed
path underneath a whole run (the batched launch every answer comes
from): a launch that returns its state unchanged, half of a launch's
lanes left out, and an answer altered where it is produced. One chip
has no exchange between chips to leave out.
"""

from __future__ import annotations

import contextlib
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import control
import graph500
import reference
from helpers import bench, tiny_run

CELLS = [c["name"] for c in bench()["workloads"]]
LAUNCH = "incremental_additions_batched"


def test_reference_matches_a_plain_dijkstra_in_float32():
    import heapq
    snaps, _, _ = graph500.evolving_keys(8, 16, 2, 20, 5, 0.57, 0.19, 0.19)
    ref = reference.Reference(snaps, 256,
                              lambda k: graph500.edge_weights(k, 9))
    got = ref.answer((0, 0), "sssp", int(snaps[0][0] // 256))
    keys = snaps[0]
    w = graph500.edge_weights(keys, 9)
    adj = {}
    for k, wt in zip(keys.tolist(), w):
        adj.setdefault(k // 256, []).append((k % 256, wt))
    dist = np.full(256, np.inf, np.float32)
    src = int(keys[0] // 256)
    dist[src] = 0
    heap = [(np.float32(0), src)]
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        for v, wt in adj.get(u, []):
            nd = np.float32(d + wt)
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(heap, (nd, v))
    np.testing.assert_array_equal(got, dist)


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_fails_the_limit(cell):
    b = bench()
    cfg_entry = next(c for c in b["configs"]
                     if c["name"] == next(w for w in b["workloads"]
                                          if w["name"] == cell)["config"])
    import json
    import run as harness
    _, config, traffic = harness.cell_files(b, cell)
    assert cfg_entry["file"].endswith(config["name"] + ".json")
    config = dict(config, scale=10, changes_per_transition=4687 >> 6)
    readings = [control.readings(config, traffic, seed, 10.0)
                for seed in (3, 2**31 + 5, 2**32 + 11)]
    json.dumps(readings)
    for r in readings:
        assert r["mismatched_values"] > r["limit"]


def _state_unchanged(res, values):
    return res._replace(values=values)


def _half_lanes(res, values):
    keep = jnp.arange(values.shape[0]) < max(1, values.shape[0] // 2)
    return res._replace(values=jnp.where(keep[:, None], res.values, values))


def _answer_altered(res, values):
    tiny = jnp.float32(np.finfo(np.float32).tiny)
    return res._replace(values=jnp.where(res.values == 0, tiny, res.values))


@contextlib.contextmanager
def broken_launch(fault):
    """Every module that holds the batched launch gets the broken one."""
    from repro.graph import engine
    original = getattr(engine, LAUNCH)

    def broken(num_nodes, semiring, values, parent, *args, **kwargs):
        return fault(original(num_nodes, semiring, values, parent, *args,
                              **kwargs), values)

    holders = [m for name, m in list(sys.modules.items())
               if name.startswith("repro") and getattr(m, LAUNCH, None)
               is original]
    for m in holders:
        setattr(m, LAUNCH, broken)
    try:
        yield
    finally:
        for m in holders:
            setattr(m, LAUNCH, original)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_lanes,
                                   _answer_altered],
                         ids=["state_unchanged", "half_lanes",
                              "answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_makes_the_run_incorrect(cell, fault):
    import repro.core.service  # noqa: F401  (load every holder first)
    import repro.core.trigrid  # noqa: F401
    with broken_launch(fault):
        result = tiny_run(cell)
    assert result["correct"] is False
    assert result["checks"]["mismatched_values"]["value"] > 0
