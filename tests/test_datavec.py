"""DataVec-equivalent tests: record readers, TransformProcess, image
pipeline, RecordReader→DataSet bridge feeding fit() end-to-end.

DL4J analogues: datavec-api transform tests, CSVRecordReader tests, and
the dl4j-examples Iris/image-classification flows.
"""
import os
import time

import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.data.iterator import AsyncDataSetIterator
from deeplearning4j_tpu.datavec import (
    CollectionRecordReader, CSVRecordReader, CSVSequenceRecordReader,
    ImageRecordReader, RecordReaderDataSetIterator, Schema,
    SequenceRecordReaderDataSetIterator, TransformProcess)
from deeplearning4j_tpu.nn.conf.layers_core import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.updaters import Adam


# ---------------------------------------------------------------- records
def test_csv_record_reader(tmp_path):
    p = tmp_path / "d.csv"
    p.write_text("# header\n1,2.5,setosa\n3,4.5,virginica\n")
    rows = list(CSVRecordReader(str(p), skip_lines=1))
    assert rows == [[1, 2.5, "setosa"], [3, 4.5, "virginica"]]


def test_csv_sequence_reader(tmp_path):
    for i in range(2):
        (tmp_path / f"s{i}.csv").write_text("1,0\n2,1\n3,0\n")
    seqs = list(CSVSequenceRecordReader(
        [str(tmp_path / "s0.csv"), str(tmp_path / "s1.csv")]))
    assert len(seqs) == 2 and len(seqs[0]) == 3


# ------------------------------------------------------------- transforms
def _iris_schema():
    return (Schema.builder()
            .add_column_double("sl", "sw", "pl", "pw")
            .add_column_categorical("species", ["setosa", "versicolor",
                                                "virginica"])
            .build())


def test_transform_process_chain_and_roundtrip():
    tp = (TransformProcess.builder(_iris_schema())
          .normalize_min_max("sl", 4.0, 8.0)
          .categorical_to_integer("species")
          .remove_columns("pw")
          .build())
    out = tp.execute([[6.0, 3.0, 1.4, 0.2, "setosa"],
                      [5.0, 2.0, 4.5, 1.5, "versicolor"]])
    assert out == [[0.5, 3.0, 1.4, 0], [0.25, 2.0, 4.5, 1]]
    assert tp.final_schema().names() == ["sl", "sw", "pl", "species"]
    tp2 = TransformProcess.from_json(tp.to_json())
    assert tp2.execute([[6.0, 3.0, 1.4, 0.2, "setosa"]]) == \
        [[0.5, 3.0, 1.4, 0]]


def test_transform_one_hot_and_filter():
    tp = (TransformProcess.builder(_iris_schema())
          .filter_invalid("sl")
          .categorical_to_one_hot("species")
          .build())
    out = tp.execute([[6.0, 3.0, 1.4, 0.2, "virginica"],
                      [float("nan"), 1, 1, 1, "setosa"]])
    assert len(out) == 1
    assert out[0][-3:] == [0.0, 0.0, 1.0]
    assert tp.final_schema().names()[-3:] == [
        "species[setosa]", "species[versicolor]", "species[virginica]"]


def test_transform_validates_eagerly():
    with pytest.raises(KeyError):
        TransformProcess.builder(_iris_schema()).remove_columns("nope") \
            .double_math_op("nope", "add", 1).build()
    with pytest.raises(ValueError):
        TransformProcess.builder(_iris_schema()) \
            .categorical_to_integer("sl").build()


# ------------------------------------------------- reader -> DataSet -> fit
def test_csv_to_fit_end_to_end(tmp_path):
    """The Iris flow: CSV file → TransformProcess → iterator → fit →
    evaluate, the canonical dl4j-examples pipeline."""
    rng = np.random.default_rng(0)
    n = 300
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = (x @ np.array([1.0, -1.0, 0.5, 0.2])) > 0
    names = ["neg", "pos"]
    lines = [",".join(f"{v:.5f}" for v in row) + f",{names[int(c)]}"
             for row, c in zip(x, y)]
    p = tmp_path / "train.csv"
    p.write_text("\n".join(lines) + "\n")

    schema = (Schema.builder().add_column_double("a", "b", "c", "d")
              .add_column_categorical("label", names).build())
    tp = (TransformProcess.builder(schema)
          .categorical_to_integer("label").build())
    it = RecordReaderDataSetIterator(
        CSVRecordReader(str(p)), batch_size=50, label_index=-1,
        n_classes=2, transform_process=tp)

    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Adam(learning_rate=0.05)).list()
            .layer(DenseLayer(n_in=4, n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .build())
    model = MultiLayerNetwork(conf).init()
    model.fit(it, n_epochs=30)
    assert model.evaluate(it).accuracy() > 0.95


def test_sequence_iterator_masks():
    reader = CollectionRecordReader([])  # placeholder; use inline seqs
    seqs = [[[0.1, 0.2, 0], [0.3, 0.4, 1]],
            [[0.5, 0.6, 1]]]

    class _SeqReader:
        def __iter__(self):
            return iter(seqs)

        def reset(self):
            pass

    it = SequenceRecordReaderDataSetIterator(_SeqReader(), batch_size=2,
                                             n_classes=2)
    ds = next(iter(it))
    assert ds.features.shape == (2, 2, 2)
    assert ds.labels.shape == (2, 2, 2)
    np.testing.assert_allclose(ds.features_mask, [[1, 1], [1, 0]])


# ----------------------------------------------------------------- images
@pytest.fixture(scope="module")
def image_tree(tmp_path_factory):
    import cv2
    root = tmp_path_factory.mktemp("imgs")
    rng = np.random.default_rng(0)
    for lab in ("cat", "dog"):
        d = root / lab
        d.mkdir()
        for i in range(12):
            img = rng.integers(0, 255, (40, 52, 3), np.uint8)
            # make classes separable: cats are red-heavy
            if lab == "cat":
                img[..., 2] = np.minimum(255, img[..., 2].astype(int) + 120).astype(np.uint8)
            cv2.imwrite(str(d / f"{i}.png"), img)
    return str(root)


def test_image_record_reader(image_tree):
    rr = ImageRecordReader(32, 32, 3, root=image_tree, shuffle_seed=0)
    assert rr.label_names == ["cat", "dog"]
    assert len(rr) == 24
    rec = next(iter(rr))
    assert rec[0].shape == (32, 32, 3) and rec[0].dtype == np.float32


def test_image_pipeline_trains(image_tree):
    rr = ImageRecordReader(16, 16, 3, root=image_tree, shuffle_seed=1)
    it = RecordReaderDataSetIterator(rr, batch_size=8, n_classes=2)
    from deeplearning4j_tpu.data.normalization import ImagePreProcessingScaler
    it.pre_processor = ImagePreProcessingScaler()
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    conf = (NeuralNetConfiguration.builder().seed(3)
            .updater(Adam(learning_rate=0.01)).list()
            .set_input_type(InputType.convolutional(16, 16, 3))
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .build())
    model = MultiLayerNetwork(conf).init()
    model.fit(it, n_epochs=20)
    assert model.evaluate(it).accuracy() > 0.9


def test_async_prefetch_overlaps_image_decode(image_tree):
    """The prefetch thread must DECODE AHEAD while the consumer computes:
    later batches are produced before the first batch's compute finishes
    (timing-robust overlap evidence, not a wall-clock race)."""
    from deeplearning4j_tpu.data.iterator import DataSetIterator

    rr = ImageRecordReader(32, 32, 3, root=image_tree)
    inner = RecordReaderDataSetIterator(rr, batch_size=6, n_classes=2)
    events = []

    class Logging(DataSetIterator):
        def __iter__(self):
            for i, ds in enumerate(inner):
                events.append(("produced", i, time.perf_counter()))
                yield ds

        def reset(self):
            inner.reset()

    compute = 0.10
    consumed0_done = None
    for i, ds in enumerate(AsyncDataSetIterator(Logging(), queue_size=2)):
        time.sleep(compute)
        if i == 0:
            consumed0_done = time.perf_counter()
    produced = {i: t for kind, i, t in events}
    assert len(produced) == 4
    # While the consumer slept on batch 0, the worker must have decoded
    # at least through batch 2 (queue_size=2 ahead + the in-flight one).
    assert produced[2] < consumed0_done, (produced, consumed0_done)


# ---------------------------------------------------------------------------
# Built-in small datasets (IrisDataSetIterator / Cifar10DataSetIterator)
# ---------------------------------------------------------------------------
def test_iris_iterator_real_data_trains():
    """The REAL in-repo Fisher iris set: a small MLP must exceed 95%
    train accuracy (it is nearly linearly separable)."""
    from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.data import IrisDataSetIterator
    from deeplearning4j_tpu.data.builtin import load_iris_arrays
    from deeplearning4j_tpu.nn.conf.layers_core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.optimize.updaters import Adam

    feats, onehot = load_iris_arrays()
    assert feats.shape == (150, 4) and onehot.shape == (150, 3)
    # spot-check two canonical rows of the published dataset
    assert np.allclose(sorted(feats[:, 0])[0], 4.3)   # min sepal length
    assert onehot.sum(0).tolist() == [50.0, 50.0, 50.0]

    it = IrisDataSetIterator(batch_size=32, seed=7)
    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Adam(learning_rate=0.02)).list()
            .layer(DenseLayer(n_in=4, n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    net.fit(it, n_epochs=60)
    acc = net.evaluate(IrisDataSetIterator(batch_size=150,
                                           shuffle=False)).accuracy()
    assert acc > 0.95, acc


def test_cifar10_iterator_shapes_and_determinism():
    from deeplearning4j_tpu.data import Cifar10DataSetIterator
    it = Cifar10DataSetIterator(64, n_examples=256, seed=3)
    assert it.is_synthetic          # no real CIFAR files in this env
    ds = next(iter(it))
    assert np.asarray(ds.features).shape == (64, 32, 32, 3)
    assert np.asarray(ds.labels).shape == (64, 10)
    assert 0.0 <= np.asarray(ds.features).min() \
        and np.asarray(ds.features).max() <= 1.0
    it2 = Cifar10DataSetIterator(64, n_examples=256, seed=3)
    np.testing.assert_array_equal(np.asarray(ds.features),
                                  np.asarray(next(iter(it2)).features))


def test_cifar10_synthetic_is_learnable():
    from deeplearning4j_tpu import NeuralNetConfiguration
    from deeplearning4j_tpu.data import Cifar10DataSetIterator
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers_conv import (
        ConvolutionLayer, GlobalPoolingLayer)
    from deeplearning4j_tpu.nn.conf.layers_core import OutputLayer
    from deeplearning4j_tpu.optimize.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(2)
            .updater(Adam(learning_rate=3e-3)).list()
            .set_input_type(InputType.convolutional(32, 32, 3))
            .layer(ConvolutionLayer(kernel_size=(3, 3),
                                    convolution_mode="same", n_out=16,
                                    activation="relu"))
            .layer(GlobalPoolingLayer(pooling_type="avg"))
            .layer(OutputLayer(n_out=10, activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    train = Cifar10DataSetIterator(64, n_examples=512, seed=5)
    net.fit(train, n_epochs=8)
    acc = net.evaluate(Cifar10DataSetIterator(
        64, train=False, n_examples=256, seed=5)).accuracy()
    assert acc > 0.5, acc           # 10-class, chance = 0.1


def test_cifar_real_binary_format_parses(tmp_path, monkeypatch):
    """The real-file CIFAR branch (round-3 review weak 7: dead code in CI)
    against a self-written fixture in the exact CIFAR-10 binary layout:
    per record 1 label byte + 3072 CHW pixel bytes."""
    rng = np.random.default_rng(0)
    n = 20
    labels = rng.integers(0, 10, n).astype(np.uint8)
    imgs_chw = rng.integers(0, 256, (n, 3, 32, 32)).astype(np.uint8)
    rec = np.concatenate(
        [labels[:, None], imgs_chw.reshape(n, -1)], axis=1)
    assert rec.shape[1] == 3073
    for name in [f"data_batch_{i}.bin" for i in range(1, 6)]:
        rec.tofile(tmp_path / name)
    rec.tofile(tmp_path / "test_batch.bin")
    monkeypatch.setenv("DL4J_TPU_CIFAR_DIR", str(tmp_path))

    from deeplearning4j_tpu.data import Cifar10DataSetIterator
    it = Cifar10DataSetIterator(16, train=False, shuffle=False)
    assert not it.is_synthetic
    ds = next(iter(it))
    assert ds.features.shape == (16, 32, 32, 3)
    # CHW binary -> NHWC float in [0,1], exact value check
    np.testing.assert_allclose(
        np.asarray(ds.features)[0],
        imgs_chw[0].transpose(1, 2, 0).astype(np.float32) / 255.0)
    np.testing.assert_array_equal(
        np.asarray(ds.labels)[:16].argmax(-1), labels[:16])
    # train split concatenates all five batch files
    tr = Cifar10DataSetIterator(32, train=True, shuffle=False)
    assert not tr.is_synthetic
    total = sum(len(np.asarray(d.features)) for d in tr)
    assert total == 5 * n
