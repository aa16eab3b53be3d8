"""Closed-loop history queries: one analyst at a time, every snapshot per query.

Each query plans the Triangular Grid (``optimal_plan``) and runs it
level by level (``run_plan_batched``, no parent tracking): the evolve
``wsb`` mode. Each query has a fresh Graph 500 search key as its source:
the deployment's keys, shuffled by the seed within blocks of eight, so
every seed asks nearly the same queries in another order. The query in flight at the deadline finishes and is counted, so
``history_query_s`` is the window's whole time over whole queries.

Traffic parameters (the mix's ``.json``): ``alg`` and ``search_keys``
(how many Graph 500 search keys are drawn for the window).
"""

from __future__ import annotations

import statistics
import time

import graph500


def setup(run) -> None:
    from repro.core.trigrid import optimal_plan, run_plan_batched
    from repro.graph.semiring import ALL_SEMIRINGS

    h = run.history
    semiring = ALL_SEMIRINGS[run.traffic["alg"]]
    sources = h.search_keys(run.traffic["search_keys"], order=run.rng(1))
    # A source with no out-edge: the warm-up query compiles and loads every
    # program the window runs while costing the device almost nothing.
    warm, = graph500.isolated_vertices(h.snapshots, h.num_nodes, 1, run.rng(4))
    res = run_plan_batched(run.store, optimal_plan(run.store), semiring, warm,
                           track_parents=False)
    _wait(res)
    run.state = {"semiring": semiring, "sources": [int(s) for s in sources]}


def _wait(res) -> None:
    for values in res.results.values():
        values.block_until_ready()


def window(run) -> None:
    from repro.core.trigrid import optimal_plan, run_plan_batched

    semiring, sources = run.state["semiring"], run.state["sources"]
    alg = run.traffic["alg"]
    launches, sweeps, seconds = [], [], []
    t_open = run.open_window()
    deadline = t_open + run.seconds
    while time.perf_counter() < deadline:
        if len(launches) == len(sources):
            raise RuntimeError(f"the window outran its {len(sources)} "
                               "search keys; raise search_keys")
        source = sources[len(launches)]
        t0 = time.perf_counter()
        with run.span("cb.query"):
            res = run_plan_batched(run.store, optimal_plan(run.store),
                                   semiring, source, track_parents=False)
            _wait(res)
        seconds.append(time.perf_counter() - t0)
        launches.append(len(res.hop_stats) + 1)
        sweeps.append(res.base_stats.sweeps
                      + sum(h.sweeps for h in res.hop_stats))
        for snap, values in sorted(res.results.items()):
            run.answer((snap, snap), alg, source, values)
    elapsed = run.close_window()
    queries = len(launches)
    run.attempted = queries
    run.e2e["history_query_s"] = elapsed / queries
    run.counters.update(queries=queries, launches=launches, sweeps=sweeps)
    run.log(f"queries: {queries}; seconds min {min(seconds):.6f} median "
            f"{statistics.median(seconds):.6f} max {max(seconds):.6f}; sweeps "
            f"min {min(sweeps)} median {statistics.median(sweeps)} max "
            f"{max(sweeps)}")
