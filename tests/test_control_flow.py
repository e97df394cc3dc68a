"""Control flow in the graph IR (round-2 review item 4).

``while_loop``/``cond`` IR nodes carry sub-SameDiff graphs in their
attrs and lower to ``jax.lax.while_loop``/``jax.lax.cond`` — the
structured-XLA replacement for the reference's TF-frame interpreter
(``org.nd4j.autodiff.samediff.internal.AbstractSession``
Switch/Merge/Enter/Exit machinery [UNVERIFIED], SURVEY §3.3).
"""
import numpy as np
import pytest

from deeplearning4j_tpu.autodiff import SameDiff


def _sum_loop():
    """while i < 5: acc += i; i += 1  (from i=0, acc=0) -> acc=10."""
    body = SameDiff.create()
    i = body.placeholder("i", (), "int32")
    acc = body.placeholder("acc", (), "float32")
    i2 = body.op("add", i, body.constant("one", np.int32(1)))
    acc2 = body.op("add", acc, body.op("cast", i, dtype="float32"))
    body.outputs = [i2.name, acc2.name]

    cond = SameDiff.create()
    ci = cond.placeholder("i", (), "int32")
    cond.placeholder("acc", (), "float32")
    lt = cond.op("less", ci, cond.constant("n", np.int32(5)))
    cond.outputs = [lt.name]

    sd = SameDiff.create()
    start = sd.placeholder("start", (), "int32")
    outs = sd.op("while_loop", start, sd.constant("z", np.float32(0)),
                 cond=cond, body=body, n_out=2)
    return sd, outs


def test_while_loop_executes():
    sd, outs = _sum_loop()
    res = sd.output({"start": np.int32(0)}, [outs[1].name])
    assert float(res[outs[1].name]) == 10.0
    res = sd.output({"start": np.int32(3)}, [outs[1].name])
    assert float(res[outs[1].name]) == 3 + 4          # i=3,4


def test_while_loop_serialization_roundtrip(tmp_path):
    sd, outs = _sum_loop()
    p = str(tmp_path / "while.sdz")
    sd.save(p)
    sd2 = SameDiff.load(p)
    res = sd2.output({"start": np.int32(0)}, [outs[1].name])
    assert float(res[outs[1].name]) == 10.0


def test_cond_executes_and_differentiates():
    then_g = SameDiff.create()
    tx = then_g.placeholder("x", (3,), "float32")
    then_g.outputs = [then_g.op(
        "mul", tx, then_g.constant("c2", np.float32(2.0))).name]
    else_g = SameDiff.create()
    ex = else_g.placeholder("x", (3,), "float32")
    else_g.outputs = [else_g.op("square", ex).name]

    sd = SameDiff.create()
    p = sd.placeholder("p", (), "bool")
    xv = sd.var("xv", np.array([1., 2., 3.], np.float32))
    co = sd.op("cond", p, xv, then=then_g, orelse=else_g, n_out=1)
    sd.set_loss_variables(sd.reduce_mean(co, name="loss"))

    np.testing.assert_allclose(
        np.asarray(sd.output({"p": np.bool_(True)}, [co.name])[co.name]),
        [2., 4., 6.])
    np.testing.assert_allclose(
        np.asarray(sd.output({"p": np.bool_(False)}, [co.name])[co.name]),
        [1., 4., 9.])
    # lax.cond is differentiable: d/dx mean(2x) = 2/3 per element
    g = sd.calculate_gradients({"p": np.bool_(True)})["xv"]
    np.testing.assert_allclose(np.asarray(g), 2.0 / 3.0, atol=1e-6)
    g = sd.calculate_gradients({"p": np.bool_(False)})["xv"]
    np.testing.assert_allclose(np.asarray(g),
                               2.0 * np.array([1., 2., 3.]) / 3.0,
                               atol=1e-6)


def test_subgraph_without_outputs_raises():
    body = SameDiff.create()
    body.placeholder("x", (), "float32")
    sd = SameDiff.create()
    p = sd.placeholder("x", (), "float32")
    out = sd.op("cond", sd.constant("t", np.bool_(True)), p,
                then=body, orelse=body, n_out=1)
    with pytest.raises(ValueError, match="no designated outputs"):
        sd.output({"x": np.float32(1)}, [out.name])


# ---------------------------------------------------------------------------
# TF v2 functional control flow import
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tf_loop_graph():
    import tensorflow as tf
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2)

    @tf.function(input_signature=[tf.TensorSpec((), tf.float32)])
    def f(x):
        i = tf.constant(0)

        def c(i, v):
            return i < 4

        def b(i, v):
            return i + 1, v * 1.5

        i, v = tf.while_loop(c, b, [i, x])
        return tf.cond(v > 5.0, lambda: v - 5.0, lambda: v + 100.0)

    frozen = convert_variables_to_constants_v2(
        f.get_concrete_function(), lower_control_flow=False)
    gd = frozen.graph.as_graph_def()
    ops = {n.op for n in gd.node}
    assert "StatelessWhile" in ops and "StatelessIf" in ops, ops
    return gd, f


def test_tf_stateless_while_if_import(tf_loop_graph):
    import tensorflow as tf
    from deeplearning4j_tpu.autodiff.tf_import import import_graph_def
    gd, f = tf_loop_graph
    sd = import_graph_def(gd)
    ph = [v.name for v in sd.vars.values()
          if v.var_type == "PLACEHOLDER"][0]
    for x in (2.0, 0.1, -3.0):
        ours = float(list(sd.output({ph: np.float32(x)}).values())[0])
        theirs = float(f(tf.constant(x, tf.float32)))
        assert abs(ours - theirs) < 1e-5, (x, ours, theirs)


def test_tf_nested_control_flow_import():
    """Regression (round-3 review): a cond INSIDE a while body needs
    the root graph's function library threaded into sub-importers."""
    import tensorflow as tf
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2)
    from deeplearning4j_tpu.autodiff.tf_import import import_graph_def

    @tf.function(input_signature=[tf.TensorSpec((), tf.float32)])
    def f(x):
        def c(i, v):
            return i < 3

        def b(i, v):
            v = tf.cond(v > 10.0, lambda: v * 0.5, lambda: v * 3.0)
            return i + 1, v

        _, v = tf.while_loop(c, b, [tf.constant(0), x])
        return v

    frozen = convert_variables_to_constants_v2(
        f.get_concrete_function(), lower_control_flow=False)
    sd = import_graph_def(frozen.graph.as_graph_def())
    ph = [v.name for v in sd.vars.values()
          if v.var_type == "PLACEHOLDER"][0]
    for x in (1.0, 7.0):
        ours = float(list(sd.output({ph: np.float32(x)}).values())[0])
        theirs = float(f(tf.constant(x, tf.float32)))
        assert abs(ours - theirs) < 1e-5, (x, ours, theirs)


def test_tf_control_flow_roundtrip(tf_loop_graph, tmp_path):
    import tensorflow as tf
    from deeplearning4j_tpu.autodiff.tf_import import import_graph_def
    gd, f = tf_loop_graph
    sd = import_graph_def(gd)
    p = str(tmp_path / "loop.sdz")
    sd.save(p)
    sd2 = SameDiff.load(p)
    ph = [v.name for v in sd2.vars.values()
          if v.var_type == "PLACEHOLDER"][0]
    ours = float(list(sd2.output({ph: np.float32(2.0)}).values())[0])
    assert abs(ours - float(f(tf.constant(2.0)))) < 1e-5


# ---------------------------------------------------------------------------
# Round-4 (round-3 review item 5): trainable bounded loops via lax.scan
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tf_trainable_loop_graph():
    """A frozen TF graph whose LOSS PATH contains a bounded while loop
    applying a trainable weight each iteration: v = v @ W (3 times)."""
    import tensorflow as tf
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2)

    w0 = np.random.default_rng(0).normal(
        scale=0.5, size=(4, 4)).astype(np.float32)
    w = tf.Variable(w0)

    @tf.function(input_signature=[tf.TensorSpec((None, 4), tf.float32)])
    def f(x):
        i = tf.constant(0)

        def c(i, v):
            return i < 3

        def b(i, v):
            return i + 1, tf.linalg.matmul(v, w)

        _, v = tf.while_loop(c, b, [i, x])
        return v

    frozen = convert_variables_to_constants_v2(
        f.get_concrete_function(), lower_control_flow=False)
    gd = frozen.graph.as_graph_def()
    # a captured tf.Variable makes TF emit stateful While (still
    # functional after freezing); the importer maps both spellings
    assert {"While", "StatelessWhile"} & {n.op for n in gd.node}
    return gd, f, w0


def test_imported_bounded_loop_scan_converts(tf_trainable_loop_graph):
    """Forward parity: the scan-converted loop matches TF."""
    import tensorflow as tf
    from deeplearning4j_tpu.autodiff.tf_import import import_graph_def
    gd, f, _ = tf_trainable_loop_graph
    sd = import_graph_def(gd)
    node = next(n for n in sd.ops if n.op_name == "while_loop")
    assert sd._while_static_pattern(node) is not None
    ph = [v.name for v in sd.vars.values()
          if v.var_type == "PLACEHOLDER"][0]
    x = np.random.default_rng(1).normal(size=(2, 4)).astype(np.float32)
    ours = np.asarray(list(sd.output({ph: x}).values())[0])
    theirs = f(tf.constant(x)).numpy()
    np.testing.assert_allclose(ours, theirs, atol=1e-5)


def test_imported_bounded_loop_finetunes(tf_trainable_loop_graph):
    """Gradients flow THROUGH the imported loop: fine-tune decreases
    the loss and moves the weight used inside the body."""
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.autodiff.tf_import import import_graph_def
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.optimize.updaters import Sgd
    gd, _, _ = tf_trainable_loop_graph
    sd = import_graph_def(gd)
    ph = [v.name for v in sd.vars.values()
          if v.var_type == "PLACEHOLDER"][0]
    out_name = [o for n in sd.ops for o in n.outputs][-1]
    tgt = sd.placeholder("target", (None, 4), "float32")
    diff = sd.op("sub", sd.vars[out_name], tgt)
    sd.set_loss_variables(sd.reduce_mean(sd.op("square", diff),
                                         name="loss"))
    sd.set_training_config(TrainingConfig(
        updater=Sgd(learning_rate=0.05),
        data_set_feature_mapping=[ph],
        data_set_label_mapping=["target"]))
    w_name = next(k for k, v in sd.vars.items()
                  if v.var_type == "VARIABLE"
                  and np.asarray(sd.values[k]).shape == (4, 4))
    before = sd.values[w_name].copy()
    rng = np.random.default_rng(2)
    x = rng.normal(size=(8, 4)).astype(np.float32)
    # achievable target: y = x @ M for a fixed M (so the loop weight
    # must move to W with W^3 ~ M)
    m = rng.normal(scale=0.5, size=(4, 4)).astype(np.float32)
    y = x @ m
    ds = MultiDataSet([x], [y])
    losses = sd.fit([ds] * 60, n_epochs=1)
    assert np.isfinite(losses).all()
    assert losses[-1] < 0.5 * losses[0], losses
    assert not np.allclose(sd.values[w_name], before)  # grads reached W


def test_unbounded_loop_raises_clear_fit_error():
    """A loop whose trip count is NOT static raises a clear ValueError
    at fit time (not a jax differentiation error mid-trace)."""
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.optimize.updaters import Sgd
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    sd, outs = _sum_loop()    # counter starts from a PLACEHOLDER
    sd.set_loss_variables(sd.reduce_mean(outs[1], name="loss"))
    sd.set_training_config(TrainingConfig(
        updater=Sgd(learning_rate=0.1),
        data_set_feature_mapping=["start"],
        data_set_label_mapping=[]))
    with pytest.raises(ValueError, match="scan-convertible"):
        sd.fit([MultiDataSet([np.int32(0)], [])], n_epochs=1)
