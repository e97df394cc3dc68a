"""Attention-fusion rewrite pass: pattern matching, parity, safety.

The rewrite connects imported graphs to the Pallas flash kernel
(round-2 review item 1a): matmul→scale→bias→softmax→matmul chains
become one ``fused_attention`` node.
"""
import numpy as np
import pytest

from deeplearning4j_tpu.autodiff import SameDiff
from deeplearning4j_tpu.autodiff.rewrites import fuse_attention


def _build_attention_ir(with_bias=True, scale_after_add=False):
    """Hand-built BERT-style attention: q/k/v placeholders [b,h,t,d]."""
    sd = SameDiff.create()
    q = sd.placeholder("q", (2, 2, 8, 4))
    k = sd.placeholder("k", (2, 2, 8, 4))
    v = sd.placeholder("v", (2, 2, 8, 4))
    s = sd.op("matmul", q, k, transpose_b=True, name="qk")
    if scale_after_add:   # invalid ordering: scale would hit the bias
        b = sd.placeholder("bias", (2, 1, 1, 8))
        s = sd.op("add", s, b, name="masked")
        s = sd.op("div", s, sd.constant("scale", np.float32(2.0)),
                  name="scaled")
    else:
        s = sd.op("div", s, sd.constant("scale", np.float32(2.0)),
                  name="scaled")
        if with_bias:
            b = sd.placeholder("bias", (2, 1, 1, 8))
            s = sd.op("add", s, b, name="masked")
        # softmax-invariant scalar add (transformers emits one)
        s = sd.op("add", s, sd.constant("zero", np.float32(0.0)),
                  name="shifted")
    p = sd.op("softmax", s, name="probs")
    p = sd.op("identity", p, name="drop")      # imported dropout
    out = sd.op("matmul", p, v, name="context")
    return sd, out.name


def _feeds(with_bias=True, seed=0):
    rng = np.random.default_rng(seed)
    f = {n: rng.normal(size=(2, 2, 8, 4)).astype(np.float32)
         for n in "qkv"}
    if with_bias:
        bias = np.zeros((2, 1, 1, 8), np.float32)
        bias[:, :, :, 6:] = -1e9
        f["bias"] = bias
    return f


def test_fuse_attention_parity_with_bias():
    sd, out_name = _build_attention_ir(with_bias=True)
    feeds = _feeds()
    before = sd.output(feeds, [out_name])[out_name]
    n = fuse_attention(sd)
    assert n == 1
    ops = [o.op_name for o in sd.ops]
    assert "fused_attention" in ops and "softmax" not in ops
    fused = next(o for o in sd.ops if o.op_name == "fused_attention")
    assert fused.attrs["scale"] == pytest.approx(0.5)   # div by 2.0
    assert len(fused.inputs) == 4                        # bias wired
    after = sd.output(feeds, [out_name])[out_name]
    np.testing.assert_allclose(np.asarray(after), np.asarray(before),
                               atol=2e-6)


def test_fuse_attention_no_bias_and_gradient():
    sd, out_name = _build_attention_ir(with_bias=False)
    feeds = _feeds(with_bias=False)
    w = sd.var("w", np.ones((4, 4), np.float32) * 0.3)
    proj = sd.op("matmul", sd.vars[out_name], w, name="proj")
    loss = sd.reduce_mean(sd.op("square", proj), name="loss")
    sd.set_loss_variables(loss)
    g_before = sd.calculate_gradients(feeds)["w"]
    assert fuse_attention(sd) == 1
    g_after = sd.calculate_gradients(feeds)["w"]
    np.testing.assert_allclose(np.asarray(g_after),
                               np.asarray(g_before), atol=2e-6)


def test_fuse_attention_rejects_scale_after_bias():
    """softmax((qk+bias)*s) != softmax(qk*s + bias): must NOT fuse."""
    sd, _ = _build_attention_ir(scale_after_add=True)
    assert fuse_attention(sd) == 0


def test_fuse_attention_rejects_multi_consumer_probs():
    """A fetched/reused probability tensor must survive the rewrite."""
    sd, _ = _build_attention_ir(with_bias=False)
    # second consumer of the softmax output
    sd.op("reduce_sum", sd.vars["probs"], name="probe")
    assert fuse_attention(sd) == 0


def test_fuse_attention_serialization_roundtrip(tmp_path):
    sd, out_name = _build_attention_ir()
    feeds = _feeds()
    fuse_attention(sd)
    before = sd.output(feeds, [out_name])[out_name]
    p = str(tmp_path / "fused.sdz")
    sd.save(p)
    sd2 = SameDiff.load(p)
    after = sd2.output(feeds, [out_name])[out_name]
    np.testing.assert_allclose(np.asarray(after), np.asarray(before),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# Imported tiny-BERT integration
# ---------------------------------------------------------------------------
import os

FIX = os.path.join(os.path.dirname(__file__), "fixtures")
PB = os.path.join(FIX, "bert_tiny_frozen.pb")
GOLD = os.path.join(FIX, "golden.npz")


def test_bert_import_fuse_attention_golden_parity():
    from deeplearning4j_tpu.autodiff.tf_import import import_frozen_pb
    sd = import_frozen_pb(PB)
    n_before = len(sd.ops)
    n = fuse_attention(sd)
    assert n == 2, n                       # one site per encoder layer
    assert len(sd.ops) < n_before
    g = np.load(GOLD)
    out = sd.output({"i": g["ids"], "m": g["mask"], "t": g["tt"]},
                    ["Identity"])
    np.testing.assert_allclose(np.asarray(out["Identity"]),
                               g["last_hidden"], atol=2e-5)


def test_bert_import_fused_finetune_step():
    """Fine-tune path trains THROUGH the fused attention node."""
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.autodiff.tf_import import import_frozen_pb
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.optimize.updaters import Adam

    sd = import_frozen_pb(PB)
    assert fuse_attention(sd) == 2
    pooled = sd.vars["Identity_1"]
    w = sd.var("cls_W", np.random.default_rng(0).normal(
        scale=0.05, size=(64, 2)).astype(np.float32))
    b = sd.var("cls_b", np.zeros(2, np.float32))
    logits = sd.op("add", sd.matmul(pooled, w), b, name="logits")
    labels = sd.placeholder("labels", (None,), "int32")
    per_ex = sd.op("sparse_softmax_cross_entropy_with_logits", labels,
                   logits)
    loss = sd.reduce_mean(per_ex, name="loss")
    sd.set_loss_variables(loss)
    sd.set_training_config(TrainingConfig(
        updater=Adam(learning_rate=1e-3),
        data_set_feature_mapping=["i", "m", "t"],
        data_set_label_mapping=["labels"]))
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 500, (8, 16)).astype(np.int32)
    ds = MultiDataSet([ids, np.ones((8, 16), np.int32),
                       np.zeros((8, 16), np.int32)],
                      [rng.integers(0, 2, 8).astype(np.int32)])
    losses = sd.fit([ds], n_epochs=8)
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


# ---------------------------------------------------------------------------
# Round-4 canonicalization passes: qkv fusion, layer-norm, gelu
# (round-3 review: imported graphs move +23% more HBM than the zoo step;
# these collapse the frozen-TF decompositions)
# ---------------------------------------------------------------------------

def test_optimize_for_tpu_on_tiny_bert_parity():
    """All four passes fire on a REAL frozen graph and preserve
    goldens: qkv groups, LayerNorms, gelus, attention sites."""
    from deeplearning4j_tpu.autodiff.rewrites import optimize_for_tpu
    from deeplearning4j_tpu.autodiff.tf_import import import_frozen_pb
    sd = import_frozen_pb(PB)
    counts = optimize_for_tpu(sd)
    assert counts["attention"] == 2, counts
    assert counts["parallel_matmuls"] == 2, counts      # qkv per layer
    assert counts["layer_norm"] == 5, counts            # emb + 2x2
    assert counts["gelu"] == 2, counts
    g = np.load(GOLD)
    out = sd.output({"i": g["ids"], "m": g["mask"], "t": g["tt"]},
                    ["Identity"])
    np.testing.assert_allclose(np.asarray(out["Identity"]),
                               g["last_hidden"], atol=3e-5)


def test_optimize_for_tpu_trains():
    """Gradients flow through all fused forms (concat-matmul-split,
    layer_norm, gelu, fused_attention): loss decreases."""
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.autodiff.rewrites import optimize_for_tpu
    from deeplearning4j_tpu.autodiff.tf_import import import_frozen_pb
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.optimize.updaters import Adam
    sd = import_frozen_pb(PB)
    optimize_for_tpu(sd)
    pooled = sd.vars["Identity_1"]
    w = sd.var("cls_W", np.random.default_rng(0).normal(
        scale=0.05, size=(64, 2)).astype(np.float32))
    b = sd.var("cls_b", np.zeros(2, np.float32))
    logits = sd.op("add", sd.matmul(pooled, w), b, name="logits")
    labels = sd.placeholder("labels", (None,), "int32")
    per_ex = sd.op("sparse_softmax_cross_entropy_with_logits", labels,
                   logits)
    sd.set_loss_variables(sd.reduce_mean(per_ex, name="loss"))
    sd.set_training_config(TrainingConfig(
        updater=Adam(learning_rate=1e-3),
        data_set_feature_mapping=["i", "m", "t"],
        data_set_label_mapping=["labels"]))
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 500, (8, 16)).astype(np.int32)
    ds = MultiDataSet([ids, np.ones((8, 16), np.int32),
                       np.zeros((8, 16), np.int32)],
                      [rng.integers(0, 2, 8).astype(np.int32)])
    losses = sd.fit([ds], n_epochs=8)
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


def test_fuse_parallel_matmuls_requires_equal_inputs():
    """Matmuls over DIFFERENT activations must not merge."""
    from deeplearning4j_tpu.autodiff.rewrites import fuse_parallel_matmuls
    sd = SameDiff.create()
    x1 = sd.placeholder("x1", (4, 8))
    x2 = sd.placeholder("x2", (4, 8))
    rng = np.random.default_rng(0)
    w1 = sd.var("w1", rng.normal(size=(8, 3)).astype(np.float32))
    w2 = sd.var("w2", rng.normal(size=(8, 5)).astype(np.float32))
    sd.op("matmul", x1, w1, name="y1")
    sd.op("matmul", x2, w2, name="y2")
    assert fuse_parallel_matmuls(sd) == 0


def test_fuse_parallel_matmuls_numerics_and_grads():
    from deeplearning4j_tpu.autodiff.rewrites import fuse_parallel_matmuls
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 8)).astype(np.float32)
    sd = SameDiff.create()
    xp = sd.placeholder("x", (None, 8))
    w1 = sd.var("w1", rng.normal(size=(8, 3)).astype(np.float32))
    w2 = sd.var("w2", rng.normal(size=(8, 5)).astype(np.float32))
    w3 = sd.var("w3", rng.normal(size=(8, 3)).astype(np.float32))
    sd.op("matmul", xp, w1, name="y1")
    sd.op("matmul", xp, w2, name="y2")
    sd.op("matmul", xp, w3, name="y3")
    base = {k: np.asarray(v) for k, v in sd.output(
        {"x": x}, ["y1", "y2", "y3"]).items()}
    assert fuse_parallel_matmuls(sd) == 1
    fused = sd.output({"x": x}, ["y1", "y2", "y3"])
    for k in base:
        np.testing.assert_allclose(np.asarray(fused[k]), base[k],
                                   atol=1e-6)
    # gradients flow to the ORIGINAL separate variables
    sd.set_loss_variables(sd.reduce_mean(
        sd.op("square", sd.vars["y2"]), name="l"))
    grads = sd.calculate_gradients({"x": x}, wrt=["w2", "w1"])
    assert np.abs(grads["w2"]).max() > 0
    np.testing.assert_allclose(grads["w1"], 0, atol=1e-7)


def test_fuse_parallel_matmuls_3d_activation_axis():
    """Review regression: a 3-D activation [b, t, d] (the ONNX
    transformer MatMul shape) must split on the LAST axis."""
    from deeplearning4j_tpu.autodiff.rewrites import fuse_parallel_matmuls
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 6, 8)).astype(np.float32)
    sd = SameDiff.create()
    xp = sd.placeholder("x", (None, 6, 8))
    w1 = sd.var("w1", rng.normal(size=(8, 3)).astype(np.float32))
    w2 = sd.var("w2", rng.normal(size=(8, 5)).astype(np.float32))
    sd.op("matmul", xp, w1, name="y1")
    sd.op("matmul", xp, w2, name="y2")
    base = {k: np.asarray(v) for k, v in sd.output(
        {"x": x}, ["y1", "y2"]).items()}
    assert base["y1"].shape == (2, 6, 3)
    assert fuse_parallel_matmuls(sd) == 1
    fused = sd.output({"x": x}, ["y1", "y2"])
    for k in base:
        assert np.asarray(fused[k]).shape == base[k].shape
        np.testing.assert_allclose(np.asarray(fused[k]), base[k],
                                   atol=1e-6)


def test_fuse_gelu_rejects_wrong_sign():
    """Review regression: (0.5*h)*erfc(+h/sqrt(2)) is h*(1-Phi(h)),
    NOT gelu — the negated inner constant must not match."""
    from deeplearning4j_tpu.autodiff.rewrites import fuse_gelu
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 8)).astype(np.float32)
    sd = SameDiff.create()
    xp = sd.placeholder("x", (None, 8))
    half = sd.constant("half", np.float32(0.5))
    c = sd.constant("c", np.float32(-0.7071067811865476))
    hm = sd.op("mul", half, xp, name="hm")
    ng = sd.op("neg", xp, name="ng")
    inner = sd.op("mul", c, ng, name="inner")   # == +x/sqrt(2)
    ec = sd.op("erfc", inner, name="ec")
    sd.op("mul", hm, ec, name="out")
    base = np.asarray(sd.output({"x": x}, ["out"])["out"])
    assert fuse_gelu(sd) == 0                   # must NOT fuse
    np.testing.assert_allclose(
        np.asarray(sd.output({"x": x}, ["out"])["out"]), base)


# ---------------------------------------------------------------------------
# Round-5: Tensordot flatten-reshape folding (round-4 review item 4 — the
# imported train step carried +293 stablehlo reshapes vs the zoo model)
# ---------------------------------------------------------------------------

def test_fold_flatten_reshapes_counts_and_parity():
    """The fold fires on every Tensordot sandwich the earlier passes
    leave (plain dense AND the fused-qkv concat weight), drops the
    orphaned shape-math chains, and preserves goldens bit-tight."""
    from collections import Counter
    from deeplearning4j_tpu.autodiff.rewrites import optimize_for_tpu
    from deeplearning4j_tpu.autodiff.tf_import import import_frozen_pb
    sd = import_frozen_pb(PB)
    pre = Counter(n.op_name for n in sd.ops)
    counts = optimize_for_tpu(sd)
    post = Counter(n.op_name for n in sd.ops)
    # tiny fixture: 2 layers x (qkv + attn-out + ff-in + ff-out) = 8
    assert counts["flatten_reshapes"] == 8, counts
    assert post["reshape"] < pre["reshape"]      # r1s + dead chains
    assert post["reduce_prod"] < pre["reduce_prod"]
    for n in sd.ops:
        if n.op_name == "matmul" and "expect_k" in n.attrs:
            assert n.attrs["expect_k"] in (64, 128)
    g = np.load(GOLD)
    out = sd.output({"i": g["ids"], "m": g["mask"], "t": g["tt"]},
                    ["Identity"])
    np.testing.assert_allclose(np.asarray(out["Identity"]),
                               g["last_hidden"], atol=3e-5)


def test_folded_matmul_expect_k_fallback():
    """expect_k on a matmul whose operand's last axis is NOT the
    contraction size re-applies the flatten (identical to the dropped
    reshape) instead of mis-contracting."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.autodiff.ops import get_op
    mm = get_op("matmul").fn
    a = jnp.arange(24, dtype=jnp.float32).reshape(2, 3, 4)
    w = jnp.ones((4, 5), jnp.float32)
    np.testing.assert_allclose(mm(a, w, expect_k=4),
                               jnp.matmul(a, w))            # innermost
    a2 = jnp.arange(24, dtype=jnp.float32).reshape(2, 2, 6)
    np.testing.assert_allclose(mm(a2, w, expect_k=4),
                               jnp.matmul(a2.reshape(-1, 4), w))


def _tensordot_split_ir(split_axis):
    """Tensordot sandwich whose matmul feeds a split: reshape(x,[6,4])
    -> matmul(W[4,6]) -> split -> reshape back to rank 3."""
    from deeplearning4j_tpu.autodiff import SameDiff
    sd = SameDiff.create()
    sd.placeholder("x", (2, 3, 4))
    shp = sd.constant("shp", np.array([6, 4], np.int64))
    flat = sd.op("reshape", sd.vars["x"], shp, name="flat")
    rng = np.random.default_rng(0)
    w = sd.var("W", value=rng.normal(size=(4, 6)).astype(np.float32))
    mm = sd.op("matmul", flat, w, name="mm")
    parts = sd.op("split", mm, n_out=2, num_split=2, axis=split_axis,
                  name="sp")
    shp2 = sd.constant("shp2", np.array([2, 3, 3], np.int64))
    outs = [sd.op("reshape", p, shp2, name=f"out{i}")
            for i, p in enumerate(parts)]
    return sd, [o.name for o in outs]


@pytest.mark.parametrize("axis,expect_folds", [(-1, 1), (1, 0)])
def test_fold_flatten_reshapes_split_axis_guard(axis, expect_folds):
    """ADVICE r5: a split with a POSITIONAL axis (resolved against the
    pre-fold rank-2 matmul output) would slice the t dimension of the
    folded rank-3 tensor — the fold must fire only for the rank-stable
    axis == -1 spelling, and numerics must be identical either way."""
    from deeplearning4j_tpu.autodiff.rewrites import fold_flatten_reshapes
    x = np.random.default_rng(1).normal(size=(2, 3, 4)).astype(np.float32)
    sd, outs = _tensordot_split_ir(axis)
    before = sd.output({"x": x}, outs)
    folds = fold_flatten_reshapes(sd)
    assert folds == expect_folds, (axis, folds)
    after = sd.output({"x": x}, outs)
    for name in outs:
        np.testing.assert_allclose(np.asarray(after[name]),
                                   np.asarray(before[name]), atol=1e-6)
