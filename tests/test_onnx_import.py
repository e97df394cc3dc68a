"""ONNX import (round-2 review missing item: ``samediff-import-onnx``).

No ``onnx`` package or onnxruntime exists in this image, so:
- the wire codec round-trips are self-tested (encode -> decode),
- the IMPORT goldens are INDEPENDENT: ONNX graphs are hand-built from
  a torch module's weights and the imported IR's outputs must match
  the torch forward elementwise.
"""
import numpy as np
import pytest

torch = pytest.importorskip("torch")

from deeplearning4j_tpu.autodiff import onnx_serde as O
from deeplearning4j_tpu.autodiff.onnx_import import (import_onnx,
                                                     import_onnx_model)


def test_wire_codec_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    w = rng.normal(size=(4, 3)).astype(np.float32)
    ints = rng.integers(-5, 5, size=7).astype(np.int64)
    m = O.model(
        [O.node("MatMul", ["x", "w"], ["y"]),
         O.node("Relu", ["y"], ["out"], alpha_test=0.5)],
        [O.value_info("x", (None, 4))],
        [O.value_info("out", (None, 3))],
        [O.tensor("w", w), O.tensor("ids", ints)])
    p = str(tmp_path / "m.onnx")
    O.save_model(m, p)
    m2 = O.load_model(p)
    assert m2["ir_version"] == 8
    assert m2["opset_import"][0]["version"] == 17
    g = m2["graph"]
    assert [n["op_type"] for n in g["node"]] == ["MatMul", "Relu"]
    assert g["node"][0]["input"] == ["x", "w"]
    np.testing.assert_array_equal(O.tensor_to_numpy(g["initializer"][0]),
                                  w)
    np.testing.assert_array_equal(O.tensor_to_numpy(g["initializer"][1]),
                                  ints)
    att = g["node"][1]["attribute"][0]
    assert att["name"] == "alpha_test" and abs(att["f"] - 0.5) < 1e-7
    # negative varints survive (two's-complement 10-byte encoding)
    assert ints.min() < 0


def test_mlp_gemm_golden_vs_torch(tmp_path):
    torch.manual_seed(0)
    net = torch.nn.Sequential(
        torch.nn.Linear(6, 16), torch.nn.ReLU(),
        torch.nn.Linear(16, 8), torch.nn.Tanh(),
        torch.nn.Linear(8, 3), torch.nn.Softmax(dim=-1))
    x = np.random.default_rng(1).normal(size=(5, 6)).astype(np.float32)
    with torch.no_grad():
        expected = net(torch.tensor(x)).numpy()

    lin = [m for m in net if isinstance(m, torch.nn.Linear)]
    inits, nodes = [], []
    prev = "x"
    for i, l in enumerate(lin):
        w = l.weight.detach().numpy()          # [out, in]
        b = l.bias.detach().numpy()
        inits += [O.tensor(f"w{i}", w), O.tensor(f"b{i}", b)]
        nodes.append(O.node("Gemm", [prev, f"w{i}", f"b{i}"],
                            [f"h{i}"], alpha=1.0, beta=1.0, transB=1))
        prev = f"h{i}"
        if i < 2:
            act = "Relu" if i == 0 else "Tanh"
            nodes.append(O.node(act, [prev], [f"a{i}"]))
            prev = f"a{i}"
    nodes.append(O.node("Softmax", [prev], ["out"], axis=-1))
    m = O.model(nodes, [O.value_info("x", (None, 6))],
                [O.value_info("out", (None, 3))], inits)
    p = str(tmp_path / "mlp.onnx")
    O.save_model(m, p)

    sd = import_onnx(p)
    got = np.asarray(sd.output({"x": x}, ["out"])["out"])
    np.testing.assert_allclose(got, expected, atol=1e-5)
    # initializers imported as trainable VARIABLEs
    assert sd.vars["w0"].var_type == "VARIABLE"


def test_cnn_golden_vs_torch(tmp_path):
    """Conv(NCHW) + BatchNorm + MaxPool + GlobalAvgPool + Gemm chain
    vs the torch forward with identical weights."""
    torch.manual_seed(1)
    conv = torch.nn.Conv2d(3, 8, 3, stride=1, padding=1)
    bn = torch.nn.BatchNorm2d(8).eval()
    bn.running_mean.data = torch.randn(8) * 0.1
    bn.running_var.data = torch.rand(8) + 0.5
    fc = torch.nn.Linear(8, 4)

    x = np.random.default_rng(2).normal(
        size=(2, 3, 8, 8)).astype(np.float32)
    with torch.no_grad():
        h = torch.relu(bn(conv(torch.tensor(x))))
        h = torch.nn.functional.max_pool2d(h, 2)
        h = h.mean(dim=(2, 3))
        expected = fc(h).numpy()

    inits = [
        O.tensor("cw", conv.weight.detach().numpy()),
        O.tensor("cb", conv.bias.detach().numpy()),
        O.tensor("g", bn.weight.detach().numpy()),
        O.tensor("beta", bn.bias.detach().numpy()),
        O.tensor("mu", bn.running_mean.detach().numpy()),
        O.tensor("var", bn.running_var.detach().numpy()),
        O.tensor("fw", fc.weight.detach().numpy()),
        O.tensor("fb", fc.bias.detach().numpy()),
    ]
    nodes = [
        O.node("Conv", ["x", "cw", "cb"], ["c"],
               strides=[1, 1], pads=[1, 1, 1, 1], group=1,
               dilations=[1, 1]),
        O.node("BatchNormalization", ["c", "g", "beta", "mu", "var"],
               ["bn"], epsilon=float(bn.eps)),
        O.node("Relu", ["bn"], ["r"]),
        O.node("MaxPool", ["r"], ["p"], kernel_shape=[2, 2],
               strides=[2, 2]),
        O.node("GlobalAveragePool", ["p"], ["gap"]),
        O.node("Flatten", ["gap"], ["fl"], axis=1),
        O.node("Gemm", ["fl", "fw", "fb"], ["out"], transB=1),
    ]
    m = O.model(nodes, [O.value_info("x", (None, 3, 8, 8))],
                [O.value_info("out", (None, 4))], inits)
    p = str(tmp_path / "cnn.onnx")
    O.save_model(m, p)
    sd = import_onnx(p)
    got = np.asarray(sd.output({"x": x}, ["out"])["out"])
    np.testing.assert_allclose(got, expected, atol=2e-5)


def test_attention_block_golden_vs_torch(tmp_path):
    """Transformer-ish subgraph (MatMul/scale/Softmax/MatMul +
    LayerNormalization) vs torch."""
    rng = np.random.default_rng(3)
    b, t, d = 2, 6, 8
    x = rng.normal(size=(b, t, d)).astype(np.float32)
    wq = rng.normal(size=(d, d)).astype(np.float32)
    wk = rng.normal(size=(d, d)).astype(np.float32)
    wv = rng.normal(size=(d, d)).astype(np.float32)
    ln_g = rng.normal(size=(d,)).astype(np.float32)
    ln_b = rng.normal(size=(d,)).astype(np.float32)

    with torch.no_grad():
        tx = torch.tensor(x)
        q = tx @ torch.tensor(wq)
        k = tx @ torch.tensor(wk)
        v = tx @ torch.tensor(wv)
        s = (q @ k.transpose(-1, -2)) / np.sqrt(d)
        att = torch.softmax(s, -1) @ v
        expected = torch.nn.functional.layer_norm(
            att, (d,), torch.tensor(ln_g), torch.tensor(ln_b)).numpy()

    inits = [O.tensor("wq", wq), O.tensor("wk", wk), O.tensor("wv", wv),
             O.tensor("ln_g", ln_g), O.tensor("ln_b", ln_b),
             O.tensor("scale", np.float32(1.0 / np.sqrt(d)))]
    nodes = [
        O.node("MatMul", ["x", "wq"], ["q"]),
        O.node("MatMul", ["x", "wk"], ["k"]),
        O.node("MatMul", ["x", "wv"], ["v"]),
        O.node("Transpose", ["k"], ["kT"], perm=[0, 2, 1]),
        O.node("MatMul", ["q", "kT"], ["qk"]),
        O.node("Mul", ["qk", "scale"], ["scaled"]),
        O.node("Softmax", ["scaled"], ["probs"], axis=-1),
        O.node("MatMul", ["probs", "v"], ["ctx"]),
        O.node("LayerNormalization", ["ctx", "ln_g", "ln_b"], ["out"],
               axis=-1, epsilon=1e-5),
    ]
    m = O.model(nodes, [O.value_info("x", (b, t, d))],
                [O.value_info("out", (b, t, d))], inits)
    sd = import_onnx_model(m)
    got = np.asarray(sd.output({"x": x}, ["out"])["out"])
    np.testing.assert_allclose(got, expected, atol=1e-5)


def test_onnx_optional_input_positions(tmp_path):
    """Round-3 review regressions: omitted OPTIONAL inputs (empty
    string) must not shift later positional inputs."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(3, 4)).astype(np.float32)
    # Clip with min omitted: clamp above only
    m = O.model([{"op_type": "Clip", "input": ["x", "", "mx"],
                  "output": ["out"], "name": "clip", "attribute": []}],
                [O.value_info("x", (3, 4))],
                [O.value_info("out", (3, 4))],
                [O.tensor("mx", np.float32(0.25))])
    sd = import_onnx_model(m)
    got = np.asarray(sd.output({"x": x}, ["out"])["out"])
    np.testing.assert_allclose(got, np.minimum(x, 0.25), atol=1e-6)
    # Slice with axes omitted but steps given
    m = O.model([{"op_type": "Slice",
                  "input": ["x", "st", "en", "", "sp"],
                  "output": ["out"], "name": "sl", "attribute": []}],
                [O.value_info("x", (3, 4))],
                [O.value_info("out", (2, 2))],
                [O.tensor("st", np.asarray([0, 0], np.int64)),
                 O.tensor("en", np.asarray([3, 4], np.int64)),
                 O.tensor("sp", np.asarray([2, 2], np.int64))])
    sd = import_onnx_model(m)
    got = np.asarray(sd.output({"x": x}, ["out"])["out"])
    np.testing.assert_allclose(got, x[::2, ::2], atol=1e-6)


def test_onnx_split_sizes_and_avg_pool_pads():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(5, 3)).astype(np.float32)
    m = O.model([O.node("Split", ["x"], ["a", "b"], axis=0,
                        split=[1, 4])],
                [O.value_info("x", (5, 3))],
                [O.value_info("a", (1, 3)), O.value_info("b", (4, 3))],
                [])
    sd = import_onnx_model(m)
    outs = sd.output({"x": x}, ["a", "b"])
    np.testing.assert_allclose(np.asarray(outs["a"]), x[:1], atol=1e-6)
    np.testing.assert_allclose(np.asarray(outs["b"]), x[1:], atol=1e-6)

    # AveragePool count_include_pad=1 with explicit pads, golden torch
    xi = rng.normal(size=(1, 2, 4, 4)).astype(np.float32)
    with torch.no_grad():
        expected = torch.nn.functional.avg_pool2d(
            torch.tensor(xi), 2, stride=2, padding=1,
            count_include_pad=True).numpy()
    m = O.model([O.node("AveragePool", ["x"], ["out"],
                        kernel_shape=[2, 2], strides=[2, 2],
                        pads=[1, 1, 1, 1], count_include_pad=1)],
                [O.value_info("x", (1, 2, 4, 4))],
                [O.value_info("out", (1, 2, 3, 3))], [])
    sd = import_onnx_model(m)
    got = np.asarray(sd.output({"x": xi}, ["out"])["out"])
    np.testing.assert_allclose(got, expected, atol=1e-5)


def test_onnx_same_lower_conv():
    """SAME_LOWER puts the odd pad at the beginning — golden via torch
    with explicit asymmetric padding."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=(1, 2, 5, 5)).astype(np.float32)
    w = rng.normal(size=(3, 2, 2, 2)).astype(np.float32)  # even kernel
    with torch.no_grad():
        xp = torch.nn.functional.pad(torch.tensor(x), (1, 0, 1, 0))
        expected = torch.nn.functional.conv2d(
            xp, torch.tensor(w)).numpy()
    m = O.model([O.node("Conv", ["x", "w"], ["out"], strides=[1, 1],
                        auto_pad="SAME_LOWER", dilations=[1, 1],
                        group=1, kernel_shape=[2, 2])],
                [O.value_info("x", (1, 2, 5, 5))],
                [O.value_info("out", (1, 3, 5, 5))],
                [O.tensor("w", w)])
    sd = import_onnx_model(m)
    got = np.asarray(sd.output({"x": x}, ["out"])["out"])
    np.testing.assert_allclose(got, expected, atol=1e-5)


def test_onnx_unknown_op_fails_loudly():
    m = O.model([O.node("TotallyMadeUp", ["x"], ["y"])],
                [O.value_info("x", (2, 2))],
                [O.value_info("y", (2, 2))], [])
    with pytest.raises(NotImplementedError, match="TotallyMadeUp"):
        import_onnx_model(m)


def test_gemm_omitted_c_as_empty_string_input():
    """ONNX encodes an omitted optional C as the empty-string input;
    Gemm must treat that as 'no C' (advisor r3)."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(4, 6)).astype(np.float32)
    w = rng.normal(size=(6, 5)).astype(np.float32)
    m = O.model([O.node("Gemm", ["x", "w", ""], ["out"],
                        alpha=1.0, beta=1.0, transA=0, transB=0)],
                [O.value_info("x", (4, 6))],
                [O.value_info("out", (4, 5))],
                [O.tensor("w", w)])
    sd = import_onnx_model(m)
    got = np.asarray(sd.output({"x": x}, ["out"])["out"])
    np.testing.assert_allclose(got, x @ w, atol=1e-5)


def test_unsqueeze_negative_axes_are_output_rank_relative():
    """axes=[-1,-3] on (2,3) -> (2,1,3,1), NOT sequential insertion
    against intermediate ranks (advisor r3)."""
    rng = np.random.default_rng(4)
    x = rng.normal(size=(2, 3)).astype(np.float32)
    m = O.model([O.node("Unsqueeze", ["x"], ["out"], axes=[-1, -3])],
                [O.value_info("x", (2, 3))],
                [O.value_info("out", (2, 1, 3, 1))], [],
                opset_version=11)
    sd = import_onnx_model(m)
    got = np.asarray(sd.output({"x": x}, ["out"])["out"])
    assert got.shape == (2, 1, 3, 1)
    np.testing.assert_allclose(got, x[:, None, :, None], atol=0)


def test_softmax_pre13_flatten_semantics():
    """Opset<13 Softmax defaults to axis=1 with flatten-to-2D
    semantics; opset>=13 is elementwise over axis=-1 (advisor r3)."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(2, 3, 4)).astype(np.float32)

    def np_softmax(a, axis):
        e = np.exp(a - a.max(axis=axis, keepdims=True))
        return e / e.sum(axis=axis, keepdims=True)

    m_old = O.model([O.node("Softmax", ["x"], ["out"])],
                    [O.value_info("x", (2, 3, 4))],
                    [O.value_info("out", (2, 3, 4))], [],
                    opset_version=11)
    got_old = np.asarray(import_onnx_model(m_old)
                         .output({"x": x}, ["out"])["out"])
    exp_old = np_softmax(x.reshape(2, 12), -1).reshape(2, 3, 4)
    np.testing.assert_allclose(got_old, exp_old, atol=1e-5)

    m_new = O.model([O.node("Softmax", ["x"], ["out"])],
                    [O.value_info("x", (2, 3, 4))],
                    [O.value_info("out", (2, 3, 4))], [],
                    opset_version=17)
    got_new = np.asarray(import_onnx_model(m_new)
                         .output({"x": x}, ["out"])["out"])
    np.testing.assert_allclose(got_new, np_softmax(x, -1), atol=1e-5)
