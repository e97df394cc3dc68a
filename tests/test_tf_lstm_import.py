"""TF RNN-cell block-op import (round-3 review missing 5): frozen graphs
from the LSTMBlockCell / dynamic_rnn era — squarely the reference's
wheelhouse (``libnd4j lstmLayer/lstmBlock`` [UNVERIFIED]) — must
import with TF-run golden parity and fine-tune."""
import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")

from deeplearning4j_tpu.autodiff.tf_import import import_graph_def


def _freeze(fn, *specs):
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2)
    conc = tf.function(fn).get_concrete_function(*specs)
    return convert_variables_to_constants_v2(
        conc).graph.as_graph_def()


def _ph(sd):
    return [v.name for v in sd.vars.values()
            if v.var_type == "PLACEHOLDER"]


def test_lstm_block_cell_golden():
    rng = np.random.default_rng(0)
    b, din, d = 3, 4, 5
    w = tf.constant(rng.normal(
        scale=0.3, size=(din + d, 4 * d)).astype(np.float32))
    bias = tf.constant(rng.normal(scale=0.1, size=(4 * d,)).astype(
        np.float32))
    z = tf.zeros((d,), tf.float32)

    def f(x, cs, h):
        return tf.raw_ops.LSTMBlockCell(
            x=x, cs_prev=cs, h_prev=h, w=w, wci=z, wcf=z, wco=z, b=bias)

    specs = [tf.TensorSpec((b, din), tf.float32),
             tf.TensorSpec((b, d), tf.float32),
             tf.TensorSpec((b, d), tf.float32)]
    gd = _freeze(f, *specs)
    assert "LSTMBlockCell" in {n.op for n in gd.node}
    sd = import_graph_def(gd)

    x = rng.normal(size=(b, din)).astype(np.float32)
    cs = rng.normal(size=(b, d)).astype(np.float32)
    h = rng.normal(size=(b, d)).astype(np.float32)
    ref = f(tf.constant(x), tf.constant(cs), tf.constant(h))
    # feed by NAME: freezing reorders placeholder nodes
    got = sd.output({"x": x, "cs": cs, "h": h})
    outs = sorted(got)           # Identity..Identity_6 in output order
    for k, r in zip(outs, ref):
        np.testing.assert_allclose(np.asarray(got[k]), r.numpy(),
                                   atol=1e-5, err_msg=k)


@pytest.mark.parametrize("raw_op,opname", [
    (lambda **kw: tf.raw_ops.BlockLSTM(forget_bias=1.0, cell_clip=3.0,
                                       **kw), "BlockLSTM"),
    (lambda **kw: tf.raw_ops.BlockLSTMV2(cell_clip=0.0, **kw),
     "BlockLSTMV2"),
])
def test_block_lstm_sequence_golden(raw_op, opname):
    """Whole-sequence LSTM (the dynamic_rnn replacement), both gate
    layouts (ICFO / IFCO)."""
    rng = np.random.default_rng(1)
    t, b, din, d = 6, 2, 3, 4
    w = tf.constant(rng.normal(
        scale=0.3, size=(din + d, 4 * d)).astype(np.float32))
    bias = tf.constant(rng.normal(scale=0.1, size=(4 * d,)).astype(
        np.float32))
    z = tf.zeros((d,), tf.float32)

    def f(x):
        zero = tf.zeros((b, d), tf.float32)
        return raw_op(seq_len_max=tf.constant(t, tf.int64), x=x,
                      cs_prev=zero, h_prev=zero, w=w, wci=z, wcf=z,
                      wco=z, b=bias)

    gd = _freeze(f, tf.TensorSpec((t, b, din), tf.float32))
    assert opname in {n.op for n in gd.node}
    sd = import_graph_def(gd)
    x = rng.normal(size=(t, b, din)).astype(np.float32)
    ref = f(tf.constant(x))
    got = sd.output({_ph(sd)[0]: x})
    for k, r in zip(sorted(got), ref):
        np.testing.assert_allclose(np.asarray(got[k]), r.numpy(),
                                   atol=1e-5, err_msg=f"{opname}:{k}")


def test_gru_block_cell_golden():
    rng = np.random.default_rng(2)
    b, din, d = 3, 4, 5
    w_ru = tf.constant(rng.normal(
        scale=0.3, size=(din + d, 2 * d)).astype(np.float32))
    w_c = tf.constant(rng.normal(
        scale=0.3, size=(din + d, d)).astype(np.float32))
    b_ru = tf.constant(rng.normal(scale=0.1, size=(2 * d,)).astype(
        np.float32))
    b_c = tf.constant(rng.normal(scale=0.1, size=(d,)).astype(
        np.float32))

    def f(x, h):
        return tf.raw_ops.GRUBlockCell(x=x, h_prev=h, w_ru=w_ru,
                                       w_c=w_c, b_ru=b_ru, b_c=b_c)

    gd = _freeze(f, tf.TensorSpec((b, din), tf.float32),
                 tf.TensorSpec((b, d), tf.float32))
    sd = import_graph_def(gd)
    x = rng.normal(size=(b, din)).astype(np.float32)
    h = rng.normal(size=(b, d)).astype(np.float32)
    ref = f(tf.constant(x), tf.constant(h))
    got = sd.output({"x": x, "h": h})
    for k, r in zip(sorted(got), ref):
        np.testing.assert_allclose(np.asarray(got[k]), r.numpy(),
                                   atol=1e-5, err_msg=k)


def test_frozen_lstm_classifier_imports_and_finetunes():
    """End-to-end 'reference wheelhouse' case: a frozen sequence
    classifier (BlockLSTM -> last hidden -> dense) imports, matches
    TF, and fine-tunes with gradients reaching the LSTM kernel."""
    rng = np.random.default_rng(3)
    t, b, din, d = 5, 4, 3, 6
    w0 = rng.normal(scale=0.3, size=(din + d, 4 * d)).astype(np.float32)
    dw0 = rng.normal(scale=0.3, size=(d, 2)).astype(np.float32)
    w = tf.Variable(w0)
    dense_w = tf.Variable(dw0)
    zb = tf.zeros((4 * d,), tf.float32)
    z = tf.zeros((d,), tf.float32)

    def f(x):
        zero = tf.zeros((b, d), tf.float32)
        outs = tf.raw_ops.BlockLSTM(
            seq_len_max=tf.constant(t, tf.int64), x=x, cs_prev=zero,
            h_prev=zero, w=w, wci=z, wcf=z, wco=z, b=zb,
            forget_bias=1.0, cell_clip=3.0)
        h_last = outs[6][-1]                  # [b, d]
        return tf.linalg.matmul(h_last, dense_w)

    gd = _freeze(f, tf.TensorSpec((t, b, din), tf.float32))
    sd = import_graph_def(gd)
    x = rng.normal(size=(t, b, din)).astype(np.float32)
    ref = f(tf.constant(x)).numpy()
    ph = _ph(sd)[0]
    out_name = "Identity"        # the frozen function's single return
    np.testing.assert_allclose(
        np.asarray(sd.output({ph: x})[out_name]), ref, atol=1e-5)

    # fine-tune: gradients must reach the LSTM kernel matrix
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.optimize.updaters import Sgd
    labels = sd.placeholder("labels", (None,), "int32")
    per_ex = sd.op("sparse_softmax_cross_entropy_with_logits", labels,
                   sd.vars[out_name])
    sd.set_loss_variables(sd.reduce_mean(per_ex, name="loss"))
    sd.set_training_config(TrainingConfig(
        updater=Sgd(learning_rate=0.1),
        data_set_feature_mapping=[ph],
        data_set_label_mapping=["labels"]))
    kern = next(k for k, v in sd.vars.items()
                if v.var_type == "VARIABLE"
                and np.asarray(sd.values[k]).shape == (din + d, 4 * d))
    before = sd.values[kern].copy()
    ds = MultiDataSet([x], [rng.integers(0, 2, b).astype(np.int32)])
    losses = sd.fit([ds] * 10, n_epochs=1)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
    assert not np.allclose(sd.values[kern], before)
