"""Without a TPU the command exits non-zero before it makes a table, and prints no result."""

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "resident_analytic_stream",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert done.returncode == 2
    assert done.stdout.strip() == ""
    assert "needs 1 TPU chip" in done.stderr
