"""Smoke-run every example script's main() for a few steps on CPU
(reference role: tests/nightly keeps the example scripts honest)."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # subprocess smoke-runs dominate suite time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")

CASES = {
    "train_mnist.py": ["--cpu", "--epochs", "1", "--batch-size", "1000",
                       "--hybridize"],
    "module_mnist.py": ["--cpu", "--epochs", "1", "--batch-size", "1000"],
    "train_cifar10_resnet.py": ["--cpu", "--steps", "2",
                                "--batch-size", "8"],
    "llama_train.py": ["--cpu", "--steps", "2", "--batch-size", "2",
                       "--seq-len", "32", "--vocab", "128",
                       "--hidden", "32", "--layers", "1"],
    "llama_generate.py": ["--cpu", "--steps", "3"],
    "llama_serve.py": ["--cpu", "--steps", "3", "--requests", "4"],
    "bert_pretrain.py": ["--cpu", "--steps", "2", "--batch-size", "2",
                         "--seq-len", "32", "--vocab", "128",
                         "--units", "32", "--layers", "1"],
    "dist_train_ps.py": ["--cpu", "--steps", "4", "--workers", "2"],
    "train_ssd.py": ["--cpu", "--steps", "6", "--batch-size", "4"],
    "dcgan.py": ["--cpu", "--steps", "4", "--batch-size", "4"],
    "lstm_bucketing.py": ["--cpu", "--steps", "9"],
    "export_serve.py": ["--cpu", "--steps", "5"],
}


@pytest.mark.parametrize("script", sorted(CASES))
def test_example_runs(script):
    env = dict(os.environ)
    # examples pass --cpu, which pins jax_platforms before the first
    # backend touch
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-u", os.path.join(EXAMPLES, script)]
        + CASES[script],
        capture_output=True, text=True, timeout=420, env=env)
    assert proc.returncode == 0, \
        f"{script} failed:\n{proc.stdout}\n{proc.stderr}"
