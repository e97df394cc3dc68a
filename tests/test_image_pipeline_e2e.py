"""Real-data image pipeline end-to-end (round-2 review item 5): on-disk
JPEG tree -> ImageRecordReader -> AsyncDataSetIterator ->
ComputationGraph.fit, plus the process-pool decode path.  The full
ImageNet-shaped throughput artifact is PIPELINE_r03.json
(scripts/bench_pipeline.py)."""
import os
import time

import numpy as np
import pytest

cv2 = pytest.importorskip("cv2")

from deeplearning4j_tpu.data.iterator import AsyncDataSetIterator
from deeplearning4j_tpu.datavec.image import ImageRecordReader
from deeplearning4j_tpu.datavec.iterator import RecordReaderDataSetIterator


@pytest.fixture(scope="module")
def jpeg_tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("imgs"))
    rng = np.random.default_rng(0)
    for c in range(3):
        d = os.path.join(root, f"class{c}")
        os.makedirs(d)
        for i in range(20):
            # class-correlated mean so a model can actually learn
            img = np.clip(rng.normal(60 + 60 * c, 30, (48, 48, 3)), 0,
                          255).astype(np.uint8)
            cv2.imwrite(os.path.join(d, f"im{i}.jpg"), img)
    return root


def test_reader_labels_from_directory_tree(jpeg_tree):
    rr = ImageRecordReader(32, 32, 3, root=jpeg_tree)
    assert rr.label_names == ["class0", "class1", "class2"]
    assert len(rr) == 60
    rec = next(iter(rr))
    assert rec[0].shape == (32, 32, 3)
    assert rec[0].dtype == np.float32


def test_process_pool_decode_matches_serial(jpeg_tree):
    serial = ImageRecordReader(32, 32, 3, root=jpeg_tree)
    pooled = ImageRecordReader(32, 32, 3, root=jpeg_tree, n_workers=2)
    for (a, la), (b, lb) in zip(serial, pooled):
        np.testing.assert_array_equal(a, b)
        assert la == lb


def test_jpeg_tree_to_graph_fit_end_to_end(jpeg_tree):
    """The full chain trains: reader -> one-hot batching -> async
    prefetch -> ComputationGraph.fit; loss drops on the separable-mean
    classes."""
    from deeplearning4j_tpu import NeuralNetConfiguration
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers_conv import (
        ConvolutionLayer, GlobalPoolingLayer)
    from deeplearning4j_tpu.nn.conf.layers_core import OutputLayer
    from deeplearning4j_tpu.optimize.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(2)
            .updater(Adam(learning_rate=3e-3))
            .graph()
            .add_inputs("in")
            .set_input_types(InputType.convolutional(32, 32, 3))
            .add_layer("c", ConvolutionLayer(kernel_size=(3, 3),
                                             convolution_mode="same",
                                             n_out=8, activation="relu"),
                       "in")
            .add_layer("gap", GlobalPoolingLayer(pooling_type="avg"), "c")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "gap")
            .set_outputs("out")
            .build())
    model = ComputationGraph(conf).init()
    rr = ImageRecordReader(32, 32, 3, root=jpeg_tree, shuffle_seed=4)
    it = AsyncDataSetIterator(
        RecordReaderDataSetIterator(rr, 16, n_classes=3), queue_size=2)
    first = model.fit(it, n_epochs=1)
    last = first
    for _ in range(12):
        last = model.fit(it, n_epochs=1)
    assert np.isfinite(last)
    assert last < first * 0.7, (first, last)
