"""The harness measures only on a TPU: elsewhere it exits non-zero and
prints no result."""
import os
import subprocess
import sys

from harness.registry import ROOT


def test_refuses_a_cpu_device():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "resnet18.steady",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr
