"""Off a TPU the command exits non-zero and prints no result; in a
directory that holds only the benchmark it does the same."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _run(cwd, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload", cell, "--seed",
         str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("cell", CELLS)
def test_no_tpu_no_result(cell):
    p = _run(ROOT, cell)
    assert p.returncode == 3, p.stderr[-2000:]
    assert "no TPU" in p.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, CELLS[0])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    json.loads((tmp_path / "BENCHMARK.json").read_text())


def test_readings_off_a_tpu_exit_non_zero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/readings.py", "--workload", CELLS[0],
         "--seeds", "1"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
