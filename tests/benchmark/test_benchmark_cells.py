"""The harness end to end, every cell, on the CPU at a tiny size
(interpret-mode kernels, four virtual devices for the four-chip cell), and
the command line refusing to measure without a TPU."""

import argparse
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import manifest, run

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny_sizes import TINY  # noqa: E402

CELLS = [w["name"] for w in manifest.load()["workloads"]]


def _run(workload, trace):
    cell = manifest.cell(workload)
    args = argparse.Namespace(workload=workload, seed=2 ** 31 + 11,
                              seconds=1.0, trace=trace)
    result = run.run(args, start=time.perf_counter(),
                     overrides=TINY[cell["config"]["job"]], allow_cpu=True)
    return cell, json.loads(json.dumps(result))      # it must serialise


@pytest.mark.parametrize("workload", CELLS)
def test_cell_end_to_end_tiny(workload):
    cell, result = _run(workload, trace=0)
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(result)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    for entry in cell["end_to_end"]:
        metric = result["metrics"][entry["name"]]
        assert metric["unit"] == entry["unit"]
        assert isinstance(metric["value"], float)
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(result["device"])
    assert result["metrics"]["setup_s"]["value"] == pytest.approx(
        result["setup_s"])


@pytest.mark.parametrize("workload", CELLS[:1] + CELLS[-1:])
def test_cell_traced_tiny(workload):
    """A traced run reports the per-layer metrics a CPU can: the five
    phases, which add up to the set-up, the counters and the compiled
    step's memory; those that need a device trace or a peak stay out."""
    cell, result = _run(workload, trace=1)
    assert result["correct"] is True, result["checks"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) <= {m["name"] for m in cell["per_layer"]}
    phases = [m["name"] for m in cell["per_layer"]
              if m["moves"] == "setup_s" and m["unit"] == "s"]
    assert len(phases) == 5
    assert sum(metrics[p] for p in phases) == pytest.approx(
        result["setup_s"])
    assert metrics["compiles_in_window"] == 0
    assert metrics["hbm_arguments_gb"] > 0
    if cell["chips"] > 1:
        assert metrics["collective_mb_per_step"] > 0


def test_command_line_without_a_tpu_fails_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    assert "found platform 'cpu'" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def test_unknown_workload_fails():
    with pytest.raises(KeyError):
        manifest.cell("no-such-cell")
