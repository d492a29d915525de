"""A run of each cell's path on the CPU at toy geometry, end to end:
archive from the seed, server, open-loop window, reference check, and
the result line's keys.  The toy cells are added from files alone."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from .conftest import REPO, TOY_CELLS


@pytest.mark.parametrize("cell", sorted(TOY_CELLS))
def test_cell_runs_and_is_correct(cpu_run, cell):
    rc, last, err = cpu_run(cell, seed=2**31 + 17)
    assert rc == 0, err
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert last["correct"] is True, last["checks"]
    assert last["failed"] == 0 and last["attempted"] == 12
    assert set(last["metrics"]) == {"products_per_s", "latency_p50_s",
                                    "latency_p90_s", "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert last["device"]["kind"] == "cpu"
    # the compared numbers are the last lines of standard error
    tail = err.strip().splitlines()[-len(last["checks"]):]
    assert [line.split(":")[0] for line in tail] == \
        [f"check {name}" for name in last["checks"]]


def test_command_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "kvnx-day.timeseries", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == b""
    assert b"no TPU" in proc.stderr


def test_command_refuses_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files
    has no system to measure."""
    import shutil

    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "kvnx-day.timeseries", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == b""
    assert b"No module named 'repro'" in proc.stderr


def test_benchmark_names_files_that_exist():
    doc = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in doc["configs"]:
        assert (REPO / c["file"]).is_file()
    for w in doc["workloads"]:
        assert (REPO / "chipbench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in doc["per_layer"]:
        assert (REPO / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
