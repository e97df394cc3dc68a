"""The examples/ surface (round-3 review item 4): every BASELINE-config
script must actually run in --smoke mode — this is dl4j-examples'
CI-run-the-examples pattern."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(ROOT, "examples")

SCRIPTS = [
    "mnist_mlp.py",
    "resnet50_training.py",
    "char_rnn.py",
    "bert_import_finetune.py",
    "data_parallel_resnet.py",
    "gpt_generate.py",
    "transfer_learning.py",
    "transfer_learning_graph.py",
]


@pytest.mark.parametrize("script", SCRIPTS)
def test_example_smoke(script):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)   # script sets cpu itself
    r = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, script), "--smoke"],
        capture_output=True, timeout=900, env=env, cwd=EXAMPLES)
    assert r.returncode == 0, (r.stdout.decode()[-1500:]
                               + r.stderr.decode()[-1500:])
    assert b"OK" in r.stdout, r.stdout.decode()[-1500:]
