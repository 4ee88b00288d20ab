"""``bench/run.py`` refuses to run where it cannot measure."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ts1-fp32.mlt-closed",
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
    )


def test_exits_nonzero_without_a_chip():
    out = _run(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
