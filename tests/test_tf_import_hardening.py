"""Importer hardening (round-2 review item 3): trainable filter, SavedModel
directories, NCHW layout insertion, FusedBatchNorm aux-output refusal."""
import os

import numpy as np
import pytest

from deeplearning4j_tpu.autodiff.tf_import import (
    import_frozen_pb, import_graph_def, import_saved_model)

FIX = os.path.join(os.path.dirname(__file__), "fixtures")
PB = os.path.join(FIX, "bert_tiny_frozen.pb")


def test_trainable_filter_controls_promotion():
    """An explicit filter decides which consts become VARIABLEs —
    the fix for the promote-everything heuristic."""
    sd_all = import_frozen_pb(PB)
    n_all = sum(1 for v in sd_all.vars.values()
                if v.var_type == "VARIABLE")

    def only_encoder_matrices(name, value):
        return "encoder" in name and value.ndim >= 2

    sd_f = import_frozen_pb(PB, trainable_filter=only_encoder_matrices)
    n_f = sum(1 for v in sd_f.vars.values() if v.var_type == "VARIABLE")
    assert 0 < n_f < n_all
    for v in sd_f.vars.values():
        if v.var_type == "VARIABLE":
            assert "encoder" in v.name
    # excluded consts execute as constants — outputs unchanged
    g = np.load(os.path.join(FIX, "golden.npz"))
    out = sd_f.output({"i": g["ids"], "m": g["mask"], "t": g["tt"]},
                      ["Identity"])
    np.testing.assert_allclose(np.asarray(out["Identity"]),
                               g["last_hidden"], atol=2e-5)


def test_saved_model_dir_import(tmp_path):
    import tensorflow as tf

    class M(tf.Module):
        def __init__(self):
            super().__init__()
            rng = np.random.default_rng(0)
            self.w1 = tf.Variable(
                rng.normal(size=(8, 16)).astype(np.float32))
            self.w2 = tf.Variable(
                rng.normal(size=(16, 4)).astype(np.float32))

        @tf.function(input_signature=[tf.TensorSpec((None, 8),
                                                    tf.float32)])
        def __call__(self, x):
            h = tf.nn.relu(tf.matmul(x, self.w1))
            return tf.nn.softmax(tf.matmul(h, self.w2))

    m = M()
    x = np.random.default_rng(1).normal(size=(3, 8)).astype(np.float32)
    expected = m(tf.constant(x)).numpy()
    path = str(tmp_path / "saved")
    tf.saved_model.save(m, path)

    sd = import_saved_model(path)
    ph = [v.name for v in sd.vars.values()
          if v.var_type == "PLACEHOLDER"]
    assert len(ph) == 1
    outs = sd.output({ph[0]: x})
    got = next(iter(outs.values()))
    np.testing.assert_allclose(np.asarray(got), expected, atol=1e-5)

    with pytest.raises(ValueError, match="no signature"):
        import_saved_model(path, signature="nope")


def _frozen_cnn(data_format):
    """Small conv+bn+pool graph in the given layout, frozen.  Weights
    are seeded so NCHW and NHWC builds share parameters."""
    import tensorflow as tf
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2)

    rng = np.random.default_rng(0)
    k = tf.constant(rng.normal(size=(3, 3, 2, 4)).astype(np.float32))
    scale = tf.constant(rng.normal(size=(4,)).astype(np.float32))
    offset = tf.constant(rng.normal(size=(4,)).astype(np.float32))
    mean = tf.constant(rng.normal(size=(4,)).astype(np.float32))
    var = tf.constant(
        np.abs(rng.normal(size=(4,))).astype(np.float32) + 0.5)

    nchw = data_format == "NCHW"
    spec = tf.TensorSpec((None, 2, 8, 8) if nchw else (None, 8, 8, 2),
                         tf.float32)

    @tf.function(input_signature=[spec])
    def f(x):
        s = [1, 1, 2, 2] if nchw else [1, 2, 2, 1]
        y = tf.nn.conv2d(x, k, strides=s, padding="SAME",
                         data_format=data_format)
        y, _, _ = tf.compat.v1.nn.fused_batch_norm(
            y, scale, offset, mean=mean, variance=var,
            is_training=False, data_format=data_format)
        ks = [1, 1, 2, 2] if nchw else [1, 2, 2, 1]
        y = tf.nn.max_pool2d(y, ksize=ks, strides=ks, padding="VALID",
                             data_format=data_format)
        return tf.nn.relu(y)

    frozen = convert_variables_to_constants_v2(f.get_concrete_function())
    return frozen.graph.as_graph_def()


def test_nchw_conv_bn_pool_import():
    """NCHW graphs import via inserted layout transposes and match the
    NHWC build of the same weights (TF CPU can't even run NCHW — the
    cross-layout parity is the strongest available golden)."""
    gd_nchw = _frozen_cnn("NCHW")
    gd_nhwc = _frozen_cnn("NHWC")
    sd_nchw = import_graph_def(gd_nchw, trainable_consts=False)
    sd_nhwc = import_graph_def(gd_nhwc, trainable_consts=False)

    rng = np.random.default_rng(2)
    x_nhwc = rng.normal(size=(2, 8, 8, 2)).astype(np.float32)
    x_nchw = np.transpose(x_nhwc, (0, 3, 1, 2))

    def run(sd, x):
        ph = [v.name for v in sd.vars.values()
              if v.var_type == "PLACEHOLDER"][0]
        return np.asarray(next(iter(sd.output({ph: x}).values())))

    out_nchw = run(sd_nchw, x_nchw)          # [b, c, h, w]
    out_nhwc = run(sd_nhwc, x_nhwc)          # [b, h, w, c]
    assert out_nchw.shape == (2, 4, 2, 2)
    np.testing.assert_allclose(np.transpose(out_nchw, (0, 2, 3, 1)),
                               out_nhwc, atol=1e-5)


def test_fused_batch_norm_training_outputs_refused():
    """A graph consuming FusedBatchNormV3's batch-statistics outputs
    must fail loudly at import, not miswire silently."""
    gd = _frozen_cnn("NHWC")
    bn = next(n for n in gd.node if n.op == "FusedBatchNormV3")
    consumer = gd.node.add()
    consumer.name = "stats_user"
    consumer.op = "Identity"
    consumer.input.append(bn.name + ":1")    # batch_mean
    with pytest.raises(NotImplementedError, match="training outputs"):
        import_graph_def(gd)
