"""``run.py`` gives no result where JAX finds no TPU."""

import os
import subprocess
import sys

import harness


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.HERE / "run.py"), "--workload",
         "ct_chor.saturated", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=harness.ROOT)
    assert proc.returncode == 3
    assert "{" not in proc.stdout
    assert "needs 1 TPU" in proc.stderr
