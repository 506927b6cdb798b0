"""The benchmark command fails, printing no result, without a chip,
and in a directory that holds only the benchmark's own files."""

import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "hotspot-paper.hybrid", "--seed", "1", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
