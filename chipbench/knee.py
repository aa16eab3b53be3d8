"""Find the service's knee: the highest session rate whose backlog does not grow.

    python3 chipbench/knee.py --workload svc.saturated --seed <n> --seconds <s> \\
        --rates 0.5 1.0 1.5

One process, one history, one warm-up; then one window per rate, each
with a fresh ``QueryService`` and no cached anchor state, so every rate
starts as a run of the cell does. For each rate it prints the offered and
answered queries per second, the backlog at each quarter of the window,
and the latency p95. Runs only on a chip, like the benchmark.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as harness


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell, config, traffic = harness.cell_files(bench, args.workload)
    harness.tpu_devices(cell["chips"])
    harness.configure_jax()
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.core.service import QueryService

    driver = harness.load_file_module(
        harness.HERE / "traffic" / f"{traffic['driver']}.py")
    run = harness.Run(cell=cell, config=config, traffic=traffic,
                      seed=args.seed, seconds=args.seconds, trace=False)
    run.compiles.install()
    harness.build_history(run)
    driver.setup(run)
    pool = sorted({s["source"] for s in run.state["sessions"]})
    for rate in sorted(args.rates):
        run.traffic = dict(traffic, sessions_per_s=rate)
        run.store.release(("AS",))
        run.answers, run.counters, run.e2e = [], {}, {}
        run.state = {
            "service": QueryService(run.store,
                                    lane_budget=config["service"]["lane_budget"]),
            "sessions": driver.plan_sessions(
                run.traffic, run.store.seq.num_snapshots,
                run.history.search_keys(traffic["source_pool"]),
                args.seconds, run.rng(1))}
        driver.window(run)
        print(json.dumps({
            "rate_sessions_per_s": rate,
            "offered_queries_per_s": run.attempted / args.seconds,
            "answered_queries_per_s": run.e2e["queries_per_s"],
            "query_p95_s": run.e2e["query_p95_s"],
            "backlog_quarters": run.counters["backlog"],
            "failed": run.failed, "window_compiles": run.window_compiles,
            "pool": len(pool)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
