"""Imported causal masks route to the causal flash kernel (round-4 review
item 6): a frozen GPT-style graph whose attention adds a [t, t]
triangular -1e9 mask constant must fuse to ``fused_attention(causal=
True)`` with the mask operand DROPPED — reaching the flash kernel's
causal path instead of being rejected as a query-dependent bias —
with golden parity and a working fine-tune."""
import os

import numpy as np
import pytest

from deeplearning4j_tpu.autodiff.rewrites import optimize_for_tpu
from deeplearning4j_tpu.autodiff.tf_import import import_frozen_pb

FIX = os.path.join(os.path.dirname(__file__), "fixtures")
PB = os.path.join(FIX, "gpt_toy_frozen.pb")
GOLD = os.path.join(FIX, "gpt_toy_golden.npz")


@pytest.fixture(scope="module")
def fused_sd():
    sd = import_frozen_pb(PB)
    stats = optimize_for_tpu(sd)
    return sd, stats


def test_causal_mask_fuses_and_drops_bias(fused_sd):
    sd, stats = fused_sd
    assert stats["attention"] == 2, stats
    fused = [n for n in sd.ops if n.op_name == "fused_attention"]
    assert len(fused) == 2
    for n in fused:
        assert n.attrs["causal"] is True
        assert len(n.inputs) == 3        # q, k, v — mask dropped


def test_causal_fused_golden_parity(fused_sd):
    sd, _ = fused_sd
    g = np.load(GOLD)
    out = sd.output({"i": g["ids"]}, ["Identity"])
    np.testing.assert_allclose(np.asarray(out["Identity"]),
                               g["last_hidden"], atol=3e-5)


def test_causal_fused_graph_finetunes_via_flash_route(fused_sd):
    """Fine-tune the causal-fused graph: grads flow through the flash
    kernel's causal path (t=512 >= the flash threshold, so the route
    probe must show 'flash' — in interpret mode on CPU)."""
    import jax.numpy as jnp
    from deeplearning4j_tpu import kernels
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.optimize.updaters import Adam

    sd = import_frozen_pb(PB)
    optimize_for_tpu(sd)
    # tiny classifier head on the mean-pooled last hidden state
    pooled = sd.reduce_mean(sd.vars["Identity"], axis=1)
    w = sd.var("cls_W", np.random.default_rng(0).normal(
        scale=0.02, size=(64, 2)).astype(np.float32))
    logits = sd.matmul(pooled, w, name="logits")
    labels = sd.placeholder("labels", (None,), "int32")
    per_ex = sd.op("sparse_softmax_cross_entropy_with_logits", labels,
                   logits)
    sd.set_loss_variables(sd.reduce_mean(per_ex, name="loss"))
    sd.set_training_config(TrainingConfig(
        updater=Adam(learning_rate=1e-3),
        data_set_feature_mapping=["i"],
        data_set_label_mapping=["labels"]))

    rng = np.random.default_rng(1)
    ids = rng.integers(0, 500, (2, 512)).astype(np.int32)
    labs = np.asarray([0, 1], np.int32)
    from deeplearning4j_tpu.data.dataset import DataSet
    kernels.reset_route_log()
    losses = sd.fit([DataSet(ids, labs)], n_epochs=3)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    routes = kernels.route_log()
    assert ("flash", 512, 32) in routes, routes


def test_fold_causal_masks_opt_out_keeps_bias_operand():
    """``optimize_for_tpu(..., fold_causal_masks=False)`` (a caller
    fine-tuning the mask): the triangular constant stays an explicit
    4th operand tagged ``bias_layout="qk"`` (a square [t, t] bias must
    not be misread as the kernel's 2-D [b, tk] padding-mask
    convention), ``causal`` stays False, and the kept-bias lowering
    computes exactly the causal path's numbers."""
    sd = import_frozen_pb(PB)
    stats = optimize_for_tpu(sd, fold_causal_masks=False)
    assert stats["attention"] == 2, stats
    fused = [n for n in sd.ops if n.op_name == "fused_attention"]
    assert len(fused) == 2
    for n in fused:
        assert n.attrs["causal"] is False
        assert n.attrs["bias_layout"] == "qk"
        assert len(n.inputs) == 4        # q, k, v, mask — kept

    # numeric equivalence at small t (the CPU-safe XLA route): the
    # declared [t, t] -1e9-triangular bias == causal=True
    from deeplearning4j_tpu.autodiff.ops import OP_REGISTRY
    fn = OP_REGISTRY["fused_attention"].fn
    rng = np.random.default_rng(0)
    q, k, v = (rng.normal(size=(2, 2, 8, 4)).astype(np.float32)
               for _ in range(3))
    mask = np.triu(np.full((8, 8), -1e9, np.float32), k=1)
    kept = fn(q, k, v, bias=mask, bias_layout="qk", scale=0.5)
    folded = fn(q, k, v, causal=True, scale=0.5)
    np.testing.assert_allclose(np.asarray(kept), np.asarray(folded),
                               atol=2e-6)
