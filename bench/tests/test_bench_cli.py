"""The benchmark measures on the chip or not at all."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

from bench import harness

ARGS = ["--workload", "search-fanout-saturate", "--seed", "3", "--seconds",
        "1", "--trace", "0"]


def _run(cwd) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _no_result(out: str) -> bool:
    return not any(line.lstrip().startswith("{") for line in out.splitlines())


def test_refuses_a_backend_other_than_tpu():
    proc = _run(harness.ROOT)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert _no_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)
