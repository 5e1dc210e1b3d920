"""``chip_smoke.py`` off a TPU: it must fail, and print no result line."""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_chip_smoke_fails_without_a_tpu():
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "not a TPU" in out.stderr
