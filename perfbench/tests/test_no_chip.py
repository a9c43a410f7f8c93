"""Without a TPU the command exits non-zero and prints no result."""

import os
import subprocess
import sys

import tiny


def test_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "tgs_kfold_train", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tiny.REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 2
    assert done.stdout.strip() == ""
    assert "no accelerator" in done.stderr
