"""The command itself: it refuses a device that is not a TPU, and a
checkout that holds only the benchmark, with a non-zero exit and no
result line."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

from fedbench import harness

ARGS = ["--workload", "charlm-sync", "--seed", "3000000123",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py"] + ARGS, cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_command_refuses_a_cpu_device():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "tpu" in p.stderr


def test_command_fails_where_only_the_benchmark_files_are():
    with tempfile.TemporaryDirectory() as d:
        d = Path(d)
        shutil.copy(harness.ROOT / "BENCHMARK.json", d)
        shutil.copytree(harness.BENCH_DIR, d / "benchmarks" / "chip",
                        ignore=shutil.ignore_patterns("__pycache__"))
        p = _run(d)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
