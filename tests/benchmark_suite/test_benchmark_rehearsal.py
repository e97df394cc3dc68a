"""A CPU rehearsal of both drivers at a tiny preset: ``run.run_cell``
(everything after the harness's look for a chip) on the sound program,
then with the timed path broken underneath -- a step that returns its
state unchanged, half of the batch left out, a served token altered --
and the control (the reference one precision down, float8 matmul
operands) put in the program's place.  ``correct`` must come out false
each time.  Nothing here is a measurement."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import correct, drivers, reference, run  # noqa: E402
from benchmark.families import post_ln  # noqa: E402

GELU = {"TransformerEncoderBlock": {"activation": "gelu"}}
TINY = dict(n_layers=2, d_model=32, n_heads=4, d_ff=64, vocab_size=64,
            max_len=64, seq_len=16)
SEED = 2 ** 31 + 11
FAMILY = "benchmark.families.post_ln"
TRAIN_CONFIG = {"family": FAMILY, "zoo_class": "deeplearning4j_tpu.zoo.bert.Bert",
                "ctor": dict(TINY, n_classes=2, compute_dtype="bfloat16"),
                "layer_overrides": GELU,
                "updater": {"type": "Adam", "learning_rate": {
                    "type": "ramp", "initial": 2e-5, "warmup": 100}},
                "adam": {"lr": 2e-5, "warmup": 100, "beta1": 0.9, "beta2": 0.999,
                         "eps": 1e-8}}
# the real cell's limits hold at the tiny size too, on this seed: the
# sound program reads grad 4e-3, change 5e-3, share 1.4e-4; the control
# 2.6e-2, 1.8e-2, 3.4e-3 (fails the share); half a batch 0.21, 0.20, 0.04
with open(os.path.join(ROOT, "benchmark", "workloads",
                       "bert-large-finetune.t512.json")) as _f:
    TRAIN_CELL = {"driver": "train", "reference_rows": 2,
                  "traffic": {"batch": 4, "seq": 16, "classes": 2, "ring": 8,
                              "labels": "one_class"},
                  "limits": json.load(_f)["limits"]}
SERVE_CONFIG = {"family": FAMILY, "zoo_class": "deeplearning4j_tpu.zoo.gpt.Gpt",
                "ctor": TINY, "layer_overrides": GELU}
SERVE_CELL = {"driver": "serve_closed",
              "server": {"compute_dtype": "bfloat16", "n_slots": 4, "max_len": 64,
                         "block_size": 8, "tick_batch": 8, "prefix_cache": True},
              "traffic": {"loop": "closed", "clients": 4,
                          "prompt_len": {"dist": "uniform", "lo": 5, "hi": 20},
                          "n_new": {"dist": "uniform", "lo": 16, "hi": 40},
                          "sizes_seed": 0, "n_sizes": 64, "ramp_seconds": 0.3,
                          "trace_seconds": 0.2, "compare_requests": 3},
              "limits": {"token_gap": 0.05}}
LAST_LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                  "window_s", "compiles_in_window", "check_s", "compared"]


def _run(cell, config, name):
    import jax
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    result = run.run_cell(name, manifest, cell, config, SEED, 0.5, 0,
                          jax.devices()[:1], {})
    assert list(result) == LAST_LINE_KEYS and list(result)[-1] == "compared"
    json.dumps(result)                       # finite numbers only
    assert set(result["compared"]) == set(cell["limits"])
    return result


def _unchanged_state(monkeypatch):
    import jax.numpy as jnp
    from deeplearning4j_tpu.optimize.solver import Solver
    monkeypatch.setattr(Solver, "step", lambda self, p, o, s, *a, **k:
                        (p, o, s, jnp.float32(0.6931)))


def _half_batch(monkeypatch):
    from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
    whole = MultiLayerNetwork._batch_dict
    monkeypatch.setattr(MultiLayerNetwork, "_batch_dict", staticmethod(
        lambda ds: {k: v[: len(v) // 2] for k, v in whole(ds).items()}))


@pytest.mark.parametrize("fault", [None, _unchanged_state, _half_batch],
                         ids=["sound", "state_unchanged", "half_batch"])
def test_train_cell_rehearsal_and_its_faults(monkeypatch, fault):
    if fault is not None:
        fault(monkeypatch)
    result = _run(TRAIN_CELL, TRAIN_CONFIG, "bert-large-finetune.t512")
    assert result["correct"] is (fault is None), result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "train_tokens_per_s"}


def _altered_token(monkeypatch):
    from deeplearning4j_tpu.parallel import generation_server
    whole = generation_server._Pending.result

    def result(self, timeout=None):
        out = np.array(whole(self, timeout))
        out[self.t0 + 2] = (out[self.t0 + 2] + 7) % 64
        return out
    monkeypatch.setattr(generation_server._Pending, "result", result)


@pytest.mark.parametrize("fault", [None, _altered_token],
                         ids=["sound", "token_altered"])
def test_serve_cell_rehearsal_and_its_fault(monkeypatch, fault):
    if fault is not None:
        fault(monkeypatch)
    result = _run(SERVE_CELL, SERVE_CONFIG, "bert-large-causal.closed-decode")
    assert result["correct"] is (fault is None), result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                      "ttft_p50_ms"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_the_control_fails_where_the_reference_passes():
    """One precision below bfloat16 (float8_e4m3 matmul operands, a
    scale per tensor) in the program's place, at the tiny size."""
    shape = post_ln.shape_of(TRAIN_CONFIG)
    ring = drivers.train_batches(TRAIN_CELL["traffic"], shape["vocab"], SEED)[:3]
    follow = lambda **kw: reference.follow_training(
        shape, TRAIN_CONFIG["adam"], SEED, ring, 2, **kw)
    ref = follow()
    ok, _ = correct.judge(correct.training_numbers(follow(), ref), TRAIN_CELL["limits"])
    bad, numbers = correct.judge(correct.training_numbers(follow(quant="fp8"), ref),
                                 TRAIN_CELL["limits"])
    assert ok and not bad, numbers
    shape = post_ln.shape_of(SERVE_CONFIG)
    w = reference.make_weights(shape, SEED)
    seq = np.random.default_rng(SEED).integers(0, 64, 60)
    # at each position of the same prompt and tokens: the token that
    # float8 puts first lies below the reference's best somewhere
    gaps = reference.served_token_gaps(w, shape["heads"], seq, 20, quant="fp8")
    assert gaps.shape == (40,) and gaps.min() >= 0.0 and gaps.max() > 0.0


def test_run_py_needs_a_tpu_and_prints_no_result_without_one():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "bert-large-finetune.t512", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no CPU fallback" in p.stderr
