"""``bench/run.py`` refuses to measure where it cannot: it exits nonzero
and prints no result line on a CPU backend, and in a directory that holds
the benchmark's files but not the program."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

from bench import harness


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi3-mini-3.8b.docqa",
         "--seed", "4000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.lstrip().startswith("{") for line in out.splitlines())


def test_no_tpu_no_result():
    proc = _run(harness.ROOT)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    for path in ("bench", "tests/bench"):
        shutil.copytree(harness.ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert _no_result(proc.stdout)
    assert "no program" in proc.stderr
