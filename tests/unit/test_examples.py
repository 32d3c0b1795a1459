"""The examples/ scripts actually run (CPU-scale smoke)."""

import os
import subprocess
import sys
import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")


def _run(script, *args):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, (
        f"{script} failed\nstdout:{proc.stdout[-2000:]}\n"
        f"stderr:{proc.stderr[-2000:]}")
    assert "final loss:" in proc.stdout
    return proc.stdout


@pytest.mark.slow
def test_bert_example():
    _run("bert_pretraining.py", "--steps", "3", "--batch", "8",
         "--seq", "32", "--model", "tiny", "--zero", "2",
         "--data_parallel", "4")


@pytest.mark.slow
def test_gpt2_pipeline_example():
    _run("gpt2_pipeline.py", "--steps", "2", "--pipe", "2", "--data", "2",
         "--layers", "4", "--micro_batch", "2", "--grad_acc", "2",
         "--seq", "32", "--vocab", "256")
