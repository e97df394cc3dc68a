"""Gradient checks + shape inference for the round-2 layer additions
(review item 8): PReLU, ElementWiseMultiplication, LocallyConnected1D/2D,
SelfAttention/LearnedSelfAttention, Convolution3D/Subsampling3D,
CenterLossOutputLayer, VariationalAutoencoder.

Model: DL4J ``GradientCheckTests``/``CNNGradientCheckTest`` — every new
layer's full training loss is vetted against centered finite differences
in float64.
"""
import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers_core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.conf.layers_misc import (
    CenterLossOutputLayer, Convolution3D, ElementWiseMultiplicationLayer,
    LearnedSelfAttentionLayer, LocallyConnected1D, LocallyConnected2D,
    PReLULayer, SelfAttentionLayer, Subsampling3DLayer,
    VariationalAutoencoder)
from deeplearning4j_tpu.nn.conf.layers_recurrent import RnnOutputLayer
from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.utils.gradient_check import check_model_gradients

rng = np.random.default_rng(7)


def _build(layers, input_type, seed=5):
    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater(Sgd(learning_rate=0.1)).list())
    for ly in layers:
        b.layer(ly)
    return MultiLayerNetwork(b.set_input_type(input_type).build()).init()


def _cls(shape, n_cls, seq=False):
    x = rng.normal(size=shape).astype(np.float64)
    if seq:
        y = np.eye(n_cls)[rng.integers(0, n_cls, (shape[0], shape[1]))]
    else:
        y = np.eye(n_cls)[rng.integers(0, n_cls, shape[0])]
    return DataSet(x, y.astype(np.float64))


def _check(model, ds):
    res = check_model_gradients(model, ds, max_per_param=12)
    assert res.passed, (res.max_rel_error, res.failures[:3])


def test_prelu_gradients_and_shape():
    m = _build([DenseLayer(n_out=6, activation="identity"),
                PReLULayer(),
                OutputLayer(n_out=3, activation="softmax", loss="mcxent")],
               InputType.feed_forward(4))
    assert m.layers[1].input_shape == (6,)
    _check(m, _cls((8, 4), 3))


def test_prelu_shared_axes():
    m = _build([PReLULayer(shared_axes=[1, 2]),
                OutputLayer(n_out=2, activation="softmax", loss="mcxent")],
               InputType.convolutional(4, 4, 3))
    assert m.params_tree["layer_0"]["alpha"].shape == (1, 1, 3)
    _check(m, _cls((4, 4, 4, 3), 2))


def test_elementwise_multiplication_gradients():
    m = _build([ElementWiseMultiplicationLayer(activation="tanh"),
                OutputLayer(n_out=3, activation="softmax", loss="mcxent")],
               InputType.feed_forward(5))
    assert m.layers[0].n_out == 5
    _check(m, _cls((8, 5), 3))


def test_locally_connected_2d():
    m = _build([LocallyConnected2D(kernel_size=(2, 2), n_out=4,
                                   activation="tanh"),
                OutputLayer(n_out=3, activation="softmax", loss="mcxent")],
               InputType.convolutional(5, 5, 2))
    # output 4x4 spatial, per-position kernels
    assert m.params_tree["layer_0"]["W"].shape == (4, 4, 8, 4)
    _check(m, _cls((4, 5, 5, 2), 3))


def test_locally_connected_1d():
    m = _build([LocallyConnected1D(kernel_size=2, n_out=4,
                                   activation="tanh"),
                RnnOutputLayer(n_out=3, activation="softmax",
                               loss="mcxent")],
               InputType.recurrent(3, timesteps=6))
    assert m.params_tree["layer_0"]["W"].shape == (5, 6, 4)
    x = rng.normal(size=(4, 6, 3)).astype(np.float64)
    y = np.eye(3)[rng.integers(0, 3, (4, 5))].astype(np.float64)
    _check(m, DataSet(x, y))


def test_self_attention_gradients_and_mask():
    m = _build([SelfAttentionLayer(n_heads=2, head_size=4,
                                   project_input=True, n_out=6),
                RnnOutputLayer(n_out=3, activation="softmax",
                               loss="mcxent")],
               InputType.recurrent(5))
    ds = _cls((4, 7, 5), 3, seq=True)
    _check(m, ds)
    # masked forward runs and masked positions don't affect others
    x = np.asarray(ds.features, np.float32)
    mask = np.ones((4, 7), np.float32)
    mask[:, 5:] = 0
    out_masked = np.asarray(m.output(x, features_mask=mask))
    x2 = x.copy()
    x2[:, 5:] = 999.0  # garbage in masked positions
    out_masked2 = np.asarray(m.output(x2, features_mask=mask))
    np.testing.assert_allclose(out_masked[:, :5], out_masked2[:, :5],
                               atol=1e-4)


def test_learned_self_attention_shapes_and_gradients():
    m = _build([LearnedSelfAttentionLayer(n_heads=2, head_size=3,
                                          n_queries=4, n_out=6),
                RnnOutputLayer(n_out=2, activation="softmax",
                               loss="mcxent")],
               InputType.recurrent(5))
    x = rng.normal(size=(3, 9, 5)).astype(np.float64)
    out = np.asarray(m.output(np.asarray(x, np.float32)))
    assert out.shape == (3, 4, 2)  # n_queries positions
    y = np.eye(2)[rng.integers(0, 2, (3, 4))].astype(np.float64)
    _check(m, DataSet(x, y))


def test_conv3d_and_subsampling3d():
    m = _build([Convolution3D(kernel_size=(2, 2, 2), n_out=4,
                              activation="relu"),
                Subsampling3DLayer(kernel_size=(2, 2, 2), stride=(2, 2, 2),
                                   pooling_type="max"),
                OutputLayer(n_out=2, activation="softmax", loss="mcxent")],
               InputType.convolutional3d(5, 5, 5, 2))
    # conv -> [4,4,4,4], pool -> [2,2,2,4], flatten -> 32
    assert m.layers[-1].n_in == 32
    _check(m, _cls((3, 5, 5, 5, 2), 2))


def test_conv3d_avg_pool_gradients():
    m = _build([Convolution3D(kernel_size=2, n_out=3, activation="tanh"),
                Subsampling3DLayer(kernel_size=2, stride=2,
                                   pooling_type="avg"),
                OutputLayer(n_out=2, activation="softmax", loss="mcxent")],
               InputType.convolutional3d(4, 4, 4, 1))
    _check(m, _cls((3, 4, 4, 4, 1), 2))


def test_center_loss_output_layer():
    m = _build([DenseLayer(n_out=6, activation="relu"),
                CenterLossOutputLayer(n_out=3, activation="softmax",
                                      loss="mcxent", lambda_=0.1)],
               InputType.feed_forward(4))
    assert m.params_tree["layer_1"]["centers"].shape == (3, 6)
    _check(m, _cls((8, 4), 3))
    # center term contributes: zero-centers loss > plain CE
    ds = _cls((16, 4), 3)
    m32 = _build([DenseLayer(n_out=6, activation="relu"),
                  CenterLossOutputLayer(n_out=3, activation="softmax",
                                        loss="mcxent", lambda_=0.1)],
                 InputType.feed_forward(4))
    losses = [m32.fit(DataSet(np.asarray(ds.features, np.float32),
                              np.asarray(ds.labels, np.float32)))
              for _ in range(30)]
    assert losses[-1] < losses[0]


def test_vae_trains_and_gradients():
    vae = VariationalAutoencoder(
        n_out=3, encoder_layer_sizes=(12,), decoder_layer_sizes=(12,),
        reconstruction_distribution="gaussian", activation="tanh")
    m = _build([vae], InputType.feed_forward(6))
    x = rng.normal(size=(16, 6)).astype(np.float64)
    _check(m, DataSet(x, x))  # deterministic (mean-field) path in f64

    # training decreases -ELBO; embedding comes out [b, n_z]
    x32 = x.astype(np.float32)
    losses = [m.fit(DataSet(x32, x32)) for _ in range(40)]
    assert losses[-1] < losses[0]
    emb = np.asarray(m.output(x32))
    assert emb.shape == (16, 3)
    rec = np.asarray(vae.reconstruct(m.params_tree["layer_0"], x32))
    assert rec.shape == x32.shape


def test_vae_bernoulli_distribution():
    vae = VariationalAutoencoder(
        n_out=2, encoder_layer_sizes=(8,), decoder_layer_sizes=(8,),
        reconstruction_distribution="bernoulli")
    m = _build([vae], InputType.feed_forward(5))
    x = (rng.random((12, 5)) > 0.5).astype(np.float64)
    _check(m, DataSet(x, x))


def test_misc_layers_serialization_roundtrip():
    from deeplearning4j_tpu.utils.model_serializer import (
        restore_multi_layer_network, write_model)
    m = _build([DenseLayer(n_out=6, activation="identity"), PReLULayer(),
                ElementWiseMultiplicationLayer(activation="tanh"),
                CenterLossOutputLayer(n_out=3, activation="softmax",
                                      loss="mcxent")],
               InputType.feed_forward(4))
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        write_model(m, f"{td}/m.zip")
        m2 = restore_multi_layer_network(f"{td}/m.zip")
        x = rng.normal(size=(4, 4)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(m.output(x)),
                                   np.asarray(m2.output(x)), rtol=1e-6)
