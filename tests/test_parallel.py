"""Sharded-trainer tests on the 8-virtual-device CPU mesh.

The DL4J analogues these replace: ParallelWrapper multi-thread tests and
the loopback-Aeron ModelParameterServer tests (SURVEY.md §4 row
"Distributed without a cluster") — here the collectives are REAL XLA
all-reduces over the forced-host-platform device mesh.
"""
import jax
import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterator import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.layers_core import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.updaters import Adam, Nesterovs
from deeplearning4j_tpu.parallel import MeshConfig, ShardedTrainer


def _toy_data(n=512, din=16, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, din)).astype(np.float32)
    w = rng.normal(size=(din, classes)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[(x @ w).argmax(-1)]
    return x, y


def _model(din=16, hidden=32, classes=4, seed=9, lr=1e-2):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=lr))
            .list()
            .layer(DenseLayer(n_in=din, n_out=hidden, activation="relu"))
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=classes, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def test_requires_8_devices():
    assert len(jax.devices()) == 8, "conftest must provide 8 CPU devices"


def test_data_parallel_training_converges():
    x, y = _toy_data()
    model = _model()
    trainer = ShardedTrainer(model, MeshConfig(data=8))
    ds = DataSet(x, y)
    it = ListDataSetIterator(ds.batch_by(64))
    trainer.fit(it, n_epochs=30)
    ev = model.evaluate(it)
    assert ev.accuracy() > 0.9, ev.stats()


def test_dp_matches_single_device_loss_sequence():
    # Same seed, same data: the 8-way sharded step must produce the same
    # loss trajectory as single-device (all-reduce == big-batch math).
    x, y = _toy_data(n=256)
    m1 = _model(seed=4)
    m2 = _model(seed=4)
    losses_single, losses_dp = [], []
    b = {"features": x, "labels": y}
    m1._build_solver()
    import jax.numpy as jnp
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    for i in range(5):
        (m1.params_tree, m1.opt_state, m1.state_tree, loss) = m1._solver.step(
            m1.params_tree, m1.opt_state, m1.state_tree, i, dict(batch),
            m1._rng.next_key())
        losses_single.append(float(loss))
    trainer = ShardedTrainer(m2, MeshConfig(data=8))
    for i in range(5):
        losses_dp.append(float(trainer.fit_batch(x, y)))
    np.testing.assert_allclose(losses_single, losses_dp, rtol=2e-4)


def test_tensor_parallel_2way_runs_and_converges():
    x, y = _toy_data()
    model = _model(hidden=64)
    trainer = ShardedTrainer(model, MeshConfig(data=4, model=2))
    # hidden kernels sharded over 'model' axis
    w1_shard = model.params_tree["layer_0"]["W"].sharding
    assert "model" in str(w1_shard.spec)
    ds = DataSet(x, y)
    it = ListDataSetIterator(ds.batch_by(64))
    trainer.fit(it, n_epochs=30)
    assert model.evaluate(it).accuracy() > 0.9


def test_graft_entry_dryrun():
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as ge
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert np.isfinite(np.asarray(out)).all()
    ge.dryrun_multichip(8)


def test_sharded_tbptt_multidataset_graph():
    """Regression: ShardedTrainer.fit over a truncated-BPTT graph fed
    MultiDataSet batches must segment time and step without error (the
    round-1 loop read DataSet-only attributes off MultiDataSet chunks)."""
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers_recurrent import LSTM, RnnOutputLayer

    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 12, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (8, 12))]
    g = (NeuralNetConfiguration.builder().seed(1)
         .updater(Adam(learning_rate=1e-2)).graph()
         .add_inputs("in").set_input_types(InputType.recurrent(6))
         .add_layer("lstm", LSTM(n_out=8), "in")
         .add_layer("out", RnnOutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "lstm")
         .set_outputs("out")
         .backprop_type("truncated_bptt", 4))
    model = ComputationGraph(g.build()).init()
    trainer = ShardedTrainer(model, MeshConfig(data=4))
    it = ListDataSetIterator([MultiDataSet([x], [y])])
    loss = trainer.fit(it, n_epochs=2)
    assert np.isfinite(loss)
    # 12 timesteps / tbptt 4 = 3 chunks per batch, 2 epochs
    assert model.iteration_count == 6


def _tiny_resnet_graph(seed=2):
    """Conv DAG with a residual add + BN — the BASELINE config 5 shape at
    toy scale (DP ResNet-50 path proof on the virtual mesh)."""
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph
    from deeplearning4j_tpu.nn.conf.graph_vertices import ElementWiseVertex
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers_conv import (
        BatchNormalization, ConvolutionLayer, GlobalPoolingLayer)
    from deeplearning4j_tpu.nn.conf.layers_core import (
        ActivationLayer, OutputLayer)

    g = (NeuralNetConfiguration.builder().seed(seed)
         .updater(Adam(learning_rate=1e-2)).graph()
         .add_inputs("in").set_input_types(InputType.convolutional(8, 8, 3)))
    g.add_layer("c1", ConvolutionLayer(kernel_size=(3, 3), n_out=8,
                                       convolution_mode="same",
                                       activation="relu"), "in")
    g.add_layer("c2", ConvolutionLayer(kernel_size=(3, 3), n_out=8,
                                       convolution_mode="same"), "c1")
    g.add_layer("bn", BatchNormalization(), "c2")
    g.add_vertex("res", ElementWiseVertex("add"), "bn", "c1")
    g.add_layer("act", ActivationLayer(activation="relu"), "res")
    g.add_layer("gap", GlobalPoolingLayer(pooling_type="avg"), "act")
    g.add_layer("out", OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"), "gap")
    return ComputationGraph(g.set_outputs("out").build()).init()


def test_dp_conv_dag_matches_single_device():
    """Data-parallel ResNet-shaped graph (conv+BN+residual) on the 8-dev
    mesh produces the SAME loss sequence as single-device training —
    global BN statistics and the gradient all-reduce included."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8, 8, 3)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 64)]

    m_single = _tiny_resnet_graph(seed=2)
    losses_single = []
    for i in range(0, 64, 16):
        from deeplearning4j_tpu.data.dataset import DataSet
        losses_single.append(m_single.fit(DataSet(x[i:i+16], y[i:i+16])))

    m_dp = _tiny_resnet_graph(seed=2)
    trainer = ShardedTrainer(m_dp, MeshConfig(data=8))
    losses_dp = [float(trainer.fit_batch(x[i:i+16], y[i:i+16]))
                 for i in range(0, 64, 16)]
    np.testing.assert_allclose(losses_dp, losses_single, rtol=2e-4)


def test_tp_excludes_conv_and_recurrent_kernels():
    """Tensor-parallel heuristic shards plain Dense kernels only: conv
    HWIO and LSTM fused-gate kernels must replicate (review weak-5)."""
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers_recurrent import (
        LSTM, RnnOutputLayer)
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph

    g = (NeuralNetConfiguration.builder().seed(1)
         .updater(Adam(learning_rate=1e-2)).graph()
         .add_inputs("in").set_input_types(InputType.recurrent(6)))
    g.add_layer("lstm", LSTM(n_out=8), "in")
    g.add_layer("dense", DenseLayer(n_out=16, activation="relu"), "lstm")
    g.add_layer("out", RnnOutputLayer(n_out=4, activation="softmax",
                                      loss="mcxent"), "dense")
    model = ComputationGraph(g.set_outputs("out").build()).init()
    trainer = ShardedTrainer(model, MeshConfig(data=2, model=2))

    def spec_of(layer, param):
        return trainer._param_shardings[layer][param].spec

    from jax.sharding import PartitionSpec as P
    assert spec_of("lstm", "W") == P()       # fused [in,4h]: replicated
    assert spec_of("lstm", "R") == P()
    assert spec_of("dense", "W") == P(None, "model")  # column parallel
    # trains fine under the mixed mesh
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 5, 6)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (8, 5))]
    loss = trainer.fit_batch(x, y)
    assert np.isfinite(float(loss))


def test_scaling_harness_emits_artifact(tmp_path):
    from deeplearning4j_tpu.parallel.scaling import measure_scaling
    import json

    def make_batch(n):
        rng = np.random.default_rng(0)
        xb = rng.normal(size=(n, 16)).astype(np.float32)
        yb = np.eye(4, dtype=np.float32)[rng.integers(0, 4, n)]
        return xb, yb

    out = str(tmp_path / "scaling.json")
    rows = measure_scaling(lambda: _model(), make_batch,
                           per_device_batch=16,
                           device_counts=[1, 2, 4, 8], n_steps=3,
                           warmup=1, out_path=out)
    assert [r["devices"] for r in rows] == [1, 2, 4, 8]
    assert all(r["examples_per_sec"] > 0 for r in rows)
    assert rows[0]["efficiency_vs_linear"] == 1.0
    data = json.load(open(out))
    assert data["metric"] == "dp_weak_scaling" and len(data["rows"]) == 4
