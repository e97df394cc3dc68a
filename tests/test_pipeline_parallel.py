"""Pipeline parallelism (GPipe over the 'pipe' mesh axis) — the last
SURVEY §2.3 strategy, new-capability territory (the reference has no
PP at all).  Exactness is the bar: the microbatched ring schedule must
match sequential block application in forward AND gradient."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from deeplearning4j_tpu.nn.conf.layers_transformer import (
    TransformerEncoderBlock)
from deeplearning4j_tpu.parallel.pipeline import (
    PipelinedTransformerLM, gpipe_apply, stack_block_params)


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:4]).reshape(4), ("pipe",))


@pytest.fixture(scope="module")
def setup(mesh):
    blk = TransformerEncoderBlock(n_heads=2, d_ff=32, use_flash=False)
    blk.infer_shapes((8, 16))
    params = stack_block_params(blk, 8, jax.random.key(0))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 8, 16)),
                    jnp.float32)
    apply_one = lambda p, a: blk.apply(p, {}, a, training=False)[0]
    return blk, params, x, apply_one


def _sequential(params, x, apply_one, n_blocks=8):
    h = x
    for i in range(n_blocks):
        h = apply_one(jax.tree_util.tree_map(lambda l: l[i], params), h)
    return h


def test_gpipe_forward_matches_sequential(mesh, setup):
    _, params, x, apply_one = setup
    ref = _sequential(params, x, apply_one)
    for n_micro in (2, 4, 8):
        out = gpipe_apply(mesh, params, x, apply_one, n_micro=n_micro)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


def test_gpipe_gradients_match_sequential(mesh, setup):
    """GPipe backward = autodiff through the scan+ppermute schedule."""
    _, params, x, apply_one = setup

    gp = jax.grad(lambda p: jnp.sum(jnp.square(
        gpipe_apply(mesh, p, x, apply_one, 4))))(params)
    gs = jax.grad(lambda p: jnp.sum(jnp.square(
        _sequential(p, x, apply_one))))(params)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4)


def test_gpipe_validates_divisibility(mesh, setup):
    blk, _, x, apply_one = setup
    bad = stack_block_params(blk, 6, jax.random.key(1))  # 6 % 4 != 0
    with pytest.raises(ValueError, match="pipeline stages"):
        gpipe_apply(mesh, bad, x, apply_one, 4)
    ok = stack_block_params(blk, 4, jax.random.key(1))
    with pytest.raises(ValueError, match="microbatches"):
        gpipe_apply(mesh, ok, x, apply_one, n_micro=3)  # 8 % 3 != 0


def test_pipelined_lm_trains(mesh):
    rng = np.random.default_rng(1)
    lm = PipelinedTransformerLM(vocab_size=40, d_model=16, n_blocks=4,
                                n_heads=2, d_ff=32, seq_len=8,
                                n_classes=2, mesh=mesh, n_micro=4,
                                lr=3e-3)
    # separable marker-token task
    ids = rng.integers(10, 40, (16, 8))
    labels = rng.integers(0, 2, 16)
    for r in range(16):
        ids[r, rng.choice(8, 2, replace=False)] = (
            rng.integers(0, 5) if labels[r] == 0 else rng.integers(5, 10))
    y = np.eye(2, dtype=np.float32)[labels]
    losses = [lm.fit_batch(ids.astype(np.int32), y) for _ in range(40)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
    acc = (lm.predict(ids.astype(np.int32)).argmax(-1) == labels).mean()
    assert acc > 0.85, acc


def test_dp_x_pp_composition_trains_and_matches():
    """DP x PP (round-3 review weak 4): MeshConfig(data=2, pipeline=4) on
    the 8-device mesh — batch sharded over 'data', blocks over
    'pipeline' — must produce the SAME losses as the pipe-only trainer
    and still learn."""
    from deeplearning4j_tpu.parallel.mesh import MeshConfig
    rng = np.random.default_rng(1)
    kw = dict(vocab_size=40, d_model=16, n_blocks=4, n_heads=2,
              d_ff=32, seq_len=8, n_classes=2, n_micro=2, lr=3e-3)
    lm = PipelinedTransformerLM.from_mesh_config(
        MeshConfig(data=2, pipeline=4), **kw)
    assert lm._data_axis == "data" and lm._pipe_axis == "pipeline"

    ids = rng.integers(10, 40, (16, 8))
    labels = rng.integers(0, 2, 16)
    for r in range(16):
        ids[r, rng.choice(8, 2, replace=False)] = (
            rng.integers(0, 5) if labels[r] == 0 else rng.integers(5, 10))
    y = np.eye(2, dtype=np.float32)[labels]

    # pipe-only reference on a 4-device pipe mesh, identical seed
    ref = PipelinedTransformerLM(
        mesh=Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("pipe",)),
        **kw)
    losses, ref_losses = [], []
    for _ in range(25):
        losses.append(lm.fit_batch(ids.astype(np.int32), y))
        ref_losses.append(ref.fit_batch(ids.astype(np.int32), y))
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-3)
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])
    acc = (lm.predict(ids.astype(np.int32)).argmax(-1) == labels).mean()
    assert acc > 0.8, acc


# ---------------------------------------------------------------------------
# Round-5 (round-4 review item 7): MeshConfig.pipeline consumed by
# ShardedTrainer for CONFIG-BUILT models — no bespoke class — and
# DP x TP x PP composing through one shard_map (TP auto-partitioned
# inside the stage body).
# ---------------------------------------------------------------------------

def _tiny_gpt_model(seed=11):
    from deeplearning4j_tpu.zoo.gpt import Gpt
    return Gpt(vocab_size=64, max_len=16, d_model=32, n_layers=4,
               n_heads=4, d_ff=64, seq_len=16, compute_dtype=None,
               use_flash=False, seed=seed).init_graph()


def _lm_batch(rng, b=16, t=16, v=64):
    x = rng.integers(0, v, (b, t)).astype(np.int32)
    y = np.roll(x, -1, axis=1)
    return x, y


@pytest.mark.parametrize("mesh_kw", [
    dict(pipeline=2),                       # pure PP
    dict(data=2, pipeline=2),               # DP x PP
    dict(data=2, model=2, pipeline=2),      # DP x TP x PP — 3 axes
])
def test_sharded_trainer_pipeline_axis_matches_single_device(mesh_kw):
    """A config-built zoo.Gpt trains through ShardedTrainer with a
    pipeline axis; its loss trajectory matches the SAME model trained
    unsharded (identical init/data) to float tolerance."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.parallel.trainer import (MeshConfig,
                                                     ShardedTrainer)
    rng = np.random.default_rng(3)
    x, y = _lm_batch(rng)
    ds = DataSet(x, y)

    ref = _tiny_gpt_model()
    ref_losses = [float(ref.fit(ds)) for _ in range(4)]

    model = _tiny_gpt_model()               # identical init (same seed)
    st = ShardedTrainer(model, MeshConfig(**mesh_kw), n_micro=2)
    losses = [float(st.fit_batch(x, y)) for _ in range(4)]

    assert np.isfinite(losses).all()
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-3, atol=2e-3)
    # trained weights flowed back into the model's own tree
    out = model.output(x)
    assert np.isfinite(np.asarray(out)).all()
    w_pipe = np.asarray(model.params_tree["layer_1"]["Wqkv"])
    w_ref = np.asarray(ref.params_tree["layer_1"]["Wqkv"])
    np.testing.assert_allclose(w_pipe, w_ref, rtol=5e-3, atol=5e-3)


def test_sharded_trainer_pipeline_sync_is_lazy():
    """ADVICE r5 perf: the per-step hot path must NOT unstack the
    pipelined blocks; the model tree refreshes on first read instead."""
    from deeplearning4j_tpu.parallel.trainer import (MeshConfig,
                                                     ShardedTrainer)
    rng = np.random.default_rng(5)
    x, y = _lm_batch(rng)
    model = _tiny_gpt_model()
    before = np.asarray(model.params_tree["layer_1"]["Wqkv"]).copy()
    st = ShardedTrainer(model, MeshConfig(pipeline=2), n_micro=2)
    st.fit_batch(x, y)
    assert st._model_stale          # step did not pay the unstack
    # the model's own tree is untouched until something reads it
    np.testing.assert_array_equal(
        before, np.asarray(model.params_tree["layer_1"]["Wqkv"]))
    out = model.output(x)           # read -> hook -> sync
    assert np.isfinite(np.asarray(out)).all()
    assert not st._model_stale
    after = np.asarray(model.params_tree["layer_1"]["Wqkv"])
    assert not np.array_equal(before, after)


def test_sharded_trainer_pipeline_validations():
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers_core import (DenseLayer,
                                                        OutputLayer)
    from deeplearning4j_tpu.parallel.trainer import (MeshConfig,
                                                     ShardedTrainer)
    conf = (NeuralNetConfiguration.builder().seed(1).list()
            .layer(DenseLayer(n_in=8, n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .build())
    m = MultiLayerNetwork(conf).init()
    with pytest.raises(ValueError, match="TransformerEncoderBlock"):
        ShardedTrainer(m, MeshConfig(pipeline=2))
    gpt = _tiny_gpt_model()                 # 4 blocks
    with pytest.raises(ValueError, match="divide"):
        ShardedTrainer(gpt, MeshConfig(pipeline=3))
