"""Without a TPU the benchmark runs nothing: a non-zero exit and no
result line."""
import os
import subprocess
import sys

import harness


def test_cpu_only_exits_nonzero_without_output():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "gemmini-dosa4.sweep-p128", "--seed", str(2**31 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr
