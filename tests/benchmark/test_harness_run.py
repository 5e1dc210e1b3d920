"""``benchmark/run.py`` refuses to run without the chip and without the program."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "catalog.study", "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "benchmark/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def _printed_a_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except ValueError:
            continue
    return False


def test_exits_without_a_chip():
    proc = _run(ROOT)
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert not _printed_a_result(proc.stdout)
    assert "no CPU fallback" in proc.stderr


def test_exits_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert not _printed_a_result(proc.stdout)
    assert "src/repro" in proc.stderr
