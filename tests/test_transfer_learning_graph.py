"""Graph-side TransferLearning (round-4 review item 5): the
``TransferLearning.GraphBuilder`` equivalent on ComputationGraph —
vertex-addressed freeze with ancestor closure, ``n_out_replace`` on a
DAG layer, remove/add vertex + new head, fine-tune config — plus
``mln_to_graph`` (upstream ``MultiLayerNetwork#toComputationGraph``)
bridging the published MLN weight sets into the DAG workflow, and the
``TransferLearningHelper`` featurizer split."""
import numpy as np
import pytest

from deeplearning4j_tpu import ComputationGraph, NeuralNetConfiguration
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.models.transfer_learning import (
    GraphBuilder, TransferLearning, TransferLearningHelper, mln_to_graph)
from deeplearning4j_tpu.nn.conf.graph_vertices import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers_core import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.updaters import Adam, Sgd


def _residual_graph(seed=5):
    g = (NeuralNetConfiguration.builder().seed(seed)
         .updater(Adam(learning_rate=1e-2))
         .graph().add_inputs("in")
         .set_input_types(InputType.feed_forward(8)))
    g.add_layer("d1", DenseLayer(n_out=16, activation="relu"), "in")
    g.add_layer("d2", DenseLayer(n_out=16, activation="relu"), "d1")
    g.add_vertex("res", ElementWiseVertex("add"), "d1", "d2")
    g.add_layer("head", DenseLayer(n_out=8, activation="relu"), "res")
    g.add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"), "head")
    return ComputationGraph(g.set_outputs("out").build()).init()


def _xy(rng, n=64, n_in=8, n_classes=2):
    x = rng.normal(size=(n, n_in)).astype(np.float32)
    labels = (x[:, 0] > 0).astype(int) if n_classes == 2 else \
        rng.integers(0, n_classes, n)
    y = np.eye(n_classes, dtype=np.float32)[labels]
    return x, y


def test_namespace_and_ancestor_closure_freeze():
    src = _residual_graph()
    assert TransferLearning.GraphBuilder is GraphBuilder
    ft = (GraphBuilder(src)
          .set_feature_extractor("res")      # freezes d1 AND d2
          .fine_tune_configuration(updater=Sgd(learning_rate=1e-2))
          .build())
    assert sorted(ft.conf.frozen_layers) == ["d1", "d2"]
    rng = np.random.default_rng(0)
    x, y = _xy(rng, n_classes=3)
    w1 = np.asarray(ft.params_tree["d1"]["W"]).copy()
    w2 = np.asarray(ft.params_tree["d2"]["W"]).copy()
    wh = np.asarray(ft.params_tree["head"]["W"]).copy()
    for _ in range(4):
        ft.fit(DataSet(x, y))
    np.testing.assert_array_equal(np.asarray(ft.params_tree["d1"]["W"]), w1)
    np.testing.assert_array_equal(np.asarray(ft.params_tree["d2"]["W"]), w2)
    assert np.abs(np.asarray(ft.params_tree["head"]["W"]) - wh).max() > 0


def test_params_copied_and_source_untouched():
    src = _residual_graph()
    rng = np.random.default_rng(1)
    x, y = _xy(rng, n_classes=3)
    src.fit(DataSet(x, y))
    w_src = np.asarray(src.params_tree["d1"]["W"]).copy()
    ft = GraphBuilder(src).set_feature_extractor("d1").build()
    np.testing.assert_array_equal(
        np.asarray(ft.params_tree["d1"]["W"]), w_src)
    ft.fit(DataSet(x, y))                    # donation must not eat src
    np.testing.assert_array_equal(
        np.asarray(src.params_tree["d1"]["W"]), w_src)
    out = src.output(x)                       # source still usable
    assert np.isfinite(np.asarray(out)).all()


def test_n_out_replace_reinitializes_dag_consumers():
    src = _residual_graph()
    ft = (GraphBuilder(src)
          .n_out_replace("head", 12)
          .build())
    assert ft.params_tree["head"]["W"].shape == (16, 12)
    assert ft.params_tree["out"]["W"].shape == (12, 3)
    # d1/d2 untouched -> copied verbatim
    np.testing.assert_array_equal(
        np.asarray(ft.params_tree["d1"]["W"]),
        np.asarray(src.params_tree["d1"]["W"]))


def test_remove_add_new_head_and_train():
    src = _residual_graph()
    ft = (GraphBuilder(src)
          .remove_vertex_and_connections("out")
          .add_layer("out2", OutputLayer(n_out=2, activation="softmax",
                                         loss="mcxent"), "head")
          .set_outputs("out2")
          .set_feature_extractor("res")
          .fine_tune_configuration(updater=Adam(learning_rate=1e-2))
          .build())
    assert "out" not in ft.conf.vertices and "out2" in ft.conf.vertices
    rng = np.random.default_rng(2)
    x, y = _xy(rng, n=128, n_classes=2)
    for _ in range(150):
        ft.fit(DataSet(x, y))
    pred = np.argmax(np.asarray(ft.output(x)), -1)
    acc = (pred == np.argmax(y, -1)).mean()
    assert acc > 0.9, acc


def test_frozen_fresh_vertex_rejected():
    src = _residual_graph()
    gb = GraphBuilder(src).n_out_replace("d2", 16)
    gb._freeze.add("d2")                    # simulate freeze-after-replace
    with pytest.raises(ValueError, match="frozen but replaced"):
        gb.build()
    with pytest.raises(ValueError, match="unknown vert"):
        GraphBuilder(src).set_feature_extractor("nope")


def test_mln_to_graph_parity_and_pretrained_finetune():
    """The published-weights workflow end to end: load the LeNet MLN
    weight set, graph-ify it, freeze the conv featurizer, swap the head
    for a binary task, fine-tune — frozen convs bit-identical, held-out
    accuracy high."""
    from deeplearning4j_tpu.zoo import load_pretrained

    mln = load_pretrained("LeNet", "mnist")
    graph = mln_to_graph(mln)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(4, 28 * 28)).astype(np.float32)
    # the MLN adapts flat input via its input-type preprocessor; the
    # graph's "input" is the cnn tensor itself
    x4 = x.reshape(-1, 28, 28, 1)
    np.testing.assert_allclose(np.asarray(mln.output(x4)),
                               np.asarray(graph.output(x4)), atol=1e-5)

    n = len(mln.layers)
    ft = (GraphBuilder(graph)
          .set_feature_extractor(f"layer_{n - 3}")
          .remove_vertex_and_connections(f"layer_{n - 1}")
          .add_layer("binary", OutputLayer(
              n_out=2, activation="softmax", loss="mcxent"),
              f"layer_{n - 2}")
          .set_outputs("binary")
          .fine_tune_configuration(updater=Adam(learning_rate=3e-3))
          .build())
    frozen_w = np.asarray(ft.params_tree["layer_0"]["W"]).copy()

    from deeplearning4j_tpu.data.mnist import MnistDataSetIterator
    it = MnistDataSetIterator(64, n_examples=512, seed=9)
    xs, labels = [], []
    for ds in it:
        f = np.asarray(ds.features).reshape(-1, 28, 28, 1)
        lab = (np.argmax(np.asarray(ds.labels), -1) < 5).astype(int)
        xs.append(f)
        labels.append(lab)
    x_all = np.concatenate(xs)
    y_all = np.eye(2, dtype=np.float32)[np.concatenate(labels)]
    tr, te = slice(0, 384), slice(384, 512)
    for _ in range(40):
        ft.fit(DataSet(x_all[tr], y_all[tr]))
    pred = np.argmax(np.asarray(ft.output(x_all[te])), -1)
    acc = (pred == np.argmax(y_all[te], -1)).mean()
    assert acc > 0.9, acc
    np.testing.assert_array_equal(
        np.asarray(ft.params_tree["layer_0"]["W"]), frozen_w)


def test_featurizer_helper_matches_head_path():
    src = _residual_graph()
    helper = TransferLearningHelper(src, "res")
    rng = np.random.default_rng(4)
    x, _ = _xy(rng, n=16, n_classes=3)
    feats = np.asarray(helper.featurize(x))
    assert feats.shape == (16, 16)
    acts = src.feed_forward(x)
    np.testing.assert_allclose(feats, np.asarray(acts["res"]), atol=1e-6)
    with pytest.raises(ValueError, match="unknown vertex"):
        TransferLearningHelper(src, "zzz")
