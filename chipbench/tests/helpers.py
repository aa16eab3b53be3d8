"""Tiny configurations of the benchmark's cells, for runs on the CPU."""

from __future__ import annotations

import json

import run as harness


def bench() -> dict:
    return json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def tiny_run(cell: str, seconds: float = 2.0, trace: bool = False,
             seed: int = 2**31 + 7, scale: int = 11) -> dict:
    """One run of ``cell`` at a small scale, through the harness, on the CPU."""
    import jax

    b = bench()
    _, config, traffic = harness.cell_files(b, cell)
    config = dict(config, scale=scale,
                  changes_per_transition=4687 >> (16 - scale))
    if "search_keys" in traffic:       # small queries: the window asks many
        traffic = dict(traffic, search_keys=400)
    return harness.execute(b, cell, seed, seconds, trace, jax.devices(),
                           config=config, traffic=traffic)
