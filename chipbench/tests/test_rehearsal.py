"""Every cell's driver runs end to end on the CPU and prints the contract's line."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

import run as harness
from helpers import bench, tiny_run

CELLS = [c["name"] for c in bench()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_result_line_shape(cell):
    result = tiny_run(cell)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    wanted = {m["name"] for m in harness.metric_entries(bench(), cell,
                                                        "end_to_end")}
    assert set(result["metrics"]) == wanted and "setup_s" in wanted
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        result["device"])
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(result)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(cell):
    result = tiny_run(cell, trace=True)
    layer = {m["name"] for m in harness.metric_entries(bench(), cell,
                                                       "per_layer")}
    # A CPU trace has no device plane: the device readers find nothing.
    readable = {n for n in layer if not n.startswith(("idle_share",
                                                      "fixpoint_device_s"))}
    assert readable and readable <= set(result["metrics"]) <= layer
    assert result["correct"] is True


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.HERE / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_refuses_without_the_program(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
