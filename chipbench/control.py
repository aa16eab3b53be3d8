"""The control of a cell's comparison: the reference one precision lower.

    python3 chipbench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed it generates the cell's history, draws the questions the
cell's traffic asks (the history cell: every snapshot from each search
key; the service cells: every window of every session), samples as many
of them as a run compares, and answers them with the plain reference
twice: in float32, as the configuration states, and in bfloat16, the
control. It prints the control's reading of ``mismatched_values`` per
seed beside the limit: a sound limit sits below every reading. The
benchmark's own runs never run this; it needs no chip and no program.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import graph500
import reference
import run as harness


def questions(config: dict, traffic: dict, seed: int, history,
              seconds: float) -> "list[tuple]":
    """``(window, alg, source)`` the cell's traffic asks, as its driver draws them."""
    import numpy as np

    if traffic["driver"] == "history":
        sources = history.search_keys(traffic["search_keys"],
                                      order=np.random.default_rng([seed, 1]))
        return [((s, s), traffic["alg"], int(src)) for src in sources
                for s in range(config["snapshots"])]
    driver = harness.load_file_module(
        harness.HERE / "traffic" / f"{traffic['driver']}.py")
    sessions = driver.plan_sessions(
        traffic, config["snapshots"],
        history.search_keys(traffic["source_pool"]), seconds,
        np.random.default_rng([seed, 1]))
    return [(w, s["alg"], s["source"]) for s in sessions for w in s["windows"]]


def readings(config: dict, traffic: dict, seed: int, seconds: float) -> dict:
    """The control's count of mismatched values on one seed's sample."""
    import numpy as np

    history = graph500.History(config, seed)
    asked = questions(config, traffic, seed, history, seconds)
    k = min(config["check"]["answers"], len(asked))
    pick = np.random.default_rng([seed, 2]).choice(len(asked), size=k,
                                                   replace=False)
    ref = reference.Reference(history.snapshots, history.num_nodes,
                              history.weights)
    mismatched = 0
    for i in sorted(pick):
        window, alg, source = asked[i]
        exact = ref.answer(window, alg, source)
        mismatched += reference.mismatched(
            ref.answer(window, alg, source, rounding="bfloat16"), exact)
    return {"seed": seed, "answers": k, "mismatched_values": mismatched,
            "limit": config["check"]["limits"]["mismatched_values"]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    _, config, traffic = harness.cell_files(bench, args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = readings(config, traffic, seed, bench["run_seconds"])
        print(json.dumps(dict(r, workload=args.workload,
                              seconds=time.perf_counter() - t0)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
