"""Ema wrapper updater — the model-averaging semantic
(ParameterAveragingTrainingMaster analogue) as an optimizer-state
transform usable from both trainers (round-2 review item 9)."""
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers_core import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize import Adam, Ema, Sgd, updater_from_dict


def test_ema_math_matches_manual_recursion():
    """update() + finalize() (the trainer contract) tracks the ACTUAL
    new parameters."""
    u = Ema(base=Sgd(learning_rate=0.5), decay=0.8)
    params = {"w": jnp.asarray([1.0, 2.0])}
    state = u.init_state(params)
    np.testing.assert_allclose(np.asarray(state["ema"]["w"]), [1.0, 2.0])
    ema_ref = np.array([1.0, 2.0])
    p_ref = np.array([1.0, 2.0])
    for step in range(3):
        grads = {"w": jnp.asarray([0.2, -0.4])}
        updates, state = u.update(grads, state, params, step)
        params = {"w": params["w"] - updates["w"]}
        state = u.finalize(state, params)
        p_ref = p_ref - 0.5 * np.array([0.2, -0.4])
        ema_ref = 0.8 * ema_ref + 0.2 * p_ref
        np.testing.assert_allclose(np.asarray(params["w"]), p_ref,
                                   atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(Ema.params_from_state(state)["w"]), ema_ref,
            atol=1e-6)


def test_ema_tracks_post_weight_decay_params():
    """Regression (round-3 review): with decoupled weightDecay the
    solver folds lr*wd*p into the updates AFTER updater.update — the
    EMA must track the decayed params exactly (decay=0 => identity)."""
    from deeplearning4j_tpu.data.dataset import DataSet
    conf = (NeuralNetConfiguration.builder().seed(3)
            .updater(Ema(base=Sgd(learning_rate=0.1), decay=0.0))
            .weight_decay(0.2)
            .list()
            .layer(DenseLayer(n_in=4, n_out=4, activation="tanh"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
    for _ in range(3):
        net.fit(DataSet(x, y))
    import jax
    for pe, pr in zip(
            jax.tree_util.tree_leaves(Ema.params_from_state(net.opt_state)),
            jax.tree_util.tree_leaves(net.params_tree)):
        np.testing.assert_allclose(np.asarray(pe), np.asarray(pr),
                                   atol=1e-7)


def test_ema_serialization_roundtrip():
    u = Ema(base=Adam(learning_rate=1e-2), decay=0.9)
    d = u.to_dict()
    u2 = updater_from_dict(d)
    assert isinstance(u2, Ema)
    assert isinstance(u2._resolved(), Adam)
    assert u2.decay == 0.9
    assert u2._resolved().learning_rate == 1e-2


def _net(updater):
    conf = (NeuralNetConfiguration.builder().seed(3).updater(updater)
            .list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def test_ema_in_multi_layer_network_training():
    from deeplearning4j_tpu.data.dataset import DataSet
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 64)]
    net = _net(Ema(base=Adam(learning_rate=1e-2), decay=0.5))
    for _ in range(10):
        net.fit(DataSet(x, y))
    ema = Ema.params_from_state(net.opt_state)
    raw = net.params_tree
    # EMA exists for every param, lags raw but is no longer the init
    leaves_e = {k: np.asarray(v) for layer in ema
                for k, v in ([(f"{layer}/{n}", a)
                              for n, a in ema[layer].items()])}
    assert leaves_e
    import jax
    for (pe, pr) in zip(jax.tree_util.tree_leaves(ema),
                        jax.tree_util.tree_leaves(raw)):
        assert pe.shape == pr.shape
        assert not np.allclose(np.asarray(pe), np.asarray(pr),
                               atol=1e-8)  # lags behind
    # averaged weights are usable: swap in and predict
    net.params_tree = ema
    out = np.asarray(net.output(x))
    assert out.shape == (64, 3)
    np.testing.assert_allclose(out.sum(1), 1.0, atol=1e-5)


def test_ema_in_sharded_trainer():
    from deeplearning4j_tpu.parallel.mesh import MeshConfig
    from deeplearning4j_tpu.parallel.trainer import ShardedTrainer
    rng = np.random.default_rng(1)
    net = _net(Ema(base=Adam(learning_rate=1e-2), decay=0.9))
    tr = ShardedTrainer(net, MeshConfig(data=4))
    for _ in range(3):
        loss = tr.fit_batch(
            rng.normal(size=(16, 8)).astype(np.float32),
            np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)])
        assert np.isfinite(float(loss))
    ema = Ema.params_from_state(net.opt_state)
    import jax
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(ema))
