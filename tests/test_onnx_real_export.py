"""ONNX import of REAL exported models (round-3 review item 3): files
produced by ``torch.onnx.export`` itself — not hand-built graphs — must
import through the in-repo wire codec, match the torch forward
elementwise, and take a fine-tune step.

No ``onnx``/``onnxscript``/``torchvision`` packages exist in this
image, so (a) export uses the TorchScript exporter with its
onnxscript-function post-pass no-opped (our graphs contain none), and
(b) the CNN is a faithful in-file ResNet-18 (conv7x7/2 + BN + maxpool +
4x2 BasicBlocks + residual downsamples + GAP + fc), exercising Conv /
BatchNormalization / MaxPool / GlobalAveragePool / Flatten / Gemm /
Add from a real exporter's opset-17 emission."""
import os

import numpy as np
import pytest

torch = pytest.importorskip("torch")

from deeplearning4j_tpu.autodiff.onnx_import import import_onnx

CACHE = os.environ.get("DL4J_TPU_FIXTURE_CACHE",
                       "/tmp/deeplearning4j_tpu_fixtures")


def _export(model, args, path, **kw):
    import torch.onnx._internal.torchscript_exporter.onnx_proto_utils \
        as opu
    orig = opu._add_onnxscript_fn
    opu._add_onnxscript_fn = lambda b, c: b   # no onnxscript functions
    try:
        torch.onnx.export(model, args, path, opset_version=17,
                          dynamo=False, **kw)
    finally:
        opu._add_onnxscript_fn = orig


class _BasicBlock(torch.nn.Module):
    def __init__(self, cin, cout, stride=1):
        super().__init__()
        self.conv1 = torch.nn.Conv2d(cin, cout, 3, stride, 1, bias=False)
        self.bn1 = torch.nn.BatchNorm2d(cout)
        self.conv2 = torch.nn.Conv2d(cout, cout, 3, 1, 1, bias=False)
        self.bn2 = torch.nn.BatchNorm2d(cout)
        self.down = None
        if stride != 1 or cin != cout:
            self.down = torch.nn.Sequential(
                torch.nn.Conv2d(cin, cout, 1, stride, bias=False),
                torch.nn.BatchNorm2d(cout))

    def forward(self, x):
        idn = x if self.down is None else self.down(x)
        y = torch.relu(self.bn1(self.conv1(x)))
        y = self.bn2(self.conv2(y))
        return torch.relu(y + idn)


class _ResNet18(torch.nn.Module):
    def __init__(self, n_classes=10):
        super().__init__()
        self.conv1 = torch.nn.Conv2d(3, 64, 7, 2, 3, bias=False)
        self.bn1 = torch.nn.BatchNorm2d(64)
        self.pool = torch.nn.MaxPool2d(3, 2, 1)
        layers, cin = [], 64
        for cout, stride in ((64, 1), (128, 2), (256, 2), (512, 2)):
            layers += [_BasicBlock(cin, cout, stride),
                       _BasicBlock(cout, cout)]
            cin = cout
        self.blocks = torch.nn.Sequential(*layers)
        self.gap = torch.nn.AdaptiveAvgPool2d(1)
        self.fc = torch.nn.Linear(512, n_classes)

    def forward(self, x):
        y = self.pool(torch.relu(self.bn1(self.conv1(x))))
        y = self.blocks(y)
        return self.fc(torch.flatten(self.gap(y), 1))


def test_torch_exported_mlp_roundtrip(tmp_path):
    torch.manual_seed(0)
    m = torch.nn.Sequential(
        torch.nn.Linear(6, 16), torch.nn.ReLU(),
        torch.nn.Linear(16, 8), torch.nn.Tanh(),
        torch.nn.Linear(8, 3))
    x = np.random.default_rng(0).normal(size=(5, 6)).astype(np.float32)
    with torch.no_grad():
        expected = m(torch.tensor(x)).numpy()
    p = str(tmp_path / "mlp.onnx")
    _export(m, (torch.tensor(x),), p, input_names=["x"],
            output_names=["out"], dynamic_axes={"x": {0: "b"}})
    sd = import_onnx(p)
    got = np.asarray(sd.output({"x": x}, ["out"])["out"])
    np.testing.assert_allclose(got, expected, atol=1e-5)


@pytest.fixture(scope="module")
def resnet18_onnx():
    os.makedirs(CACHE, exist_ok=True)
    p = os.path.join(CACHE, "resnet18_torch_export.onnx")
    g = os.path.join(CACHE, "resnet18_torch_golden.npz")
    if not (os.path.exists(p) and os.path.exists(g)):
        torch.manual_seed(0)
        m = _ResNet18().eval()
        x = np.random.default_rng(1).normal(
            size=(2, 3, 64, 64)).astype(np.float32)
        with torch.no_grad():
            expected = m(torch.tensor(x)).numpy()
        _export(m, (torch.tensor(x),), p, input_names=["x"],
                output_names=["out"])
        np.savez(g, x=x, expected=expected)
    return p, np.load(g)


def test_torch_exported_resnet18_parity(resnet18_onnx):
    p, g = resnet18_onnx
    sd = import_onnx(p)
    got = np.asarray(sd.output({"x": g["x"]}, ["out"])["out"])
    np.testing.assert_allclose(got, g["expected"], atol=5e-4)


def test_torch_exported_resnet18_finetune_step(resnet18_onnx):
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.optimize.updaters import Sgd
    p, g = resnet18_onnx
    sd = import_onnx(p)
    labels = sd.placeholder("labels", (None,), "int32")
    per_ex = sd.op("sparse_softmax_cross_entropy_with_logits", labels,
                   sd.vars["out"])
    sd.set_loss_variables(sd.reduce_mean(per_ex, name="loss"))
    sd.set_training_config(TrainingConfig(
        updater=Sgd(learning_rate=1e-3),
        data_set_feature_mapping=["x"],
        data_set_label_mapping=["labels"]))
    probe = next(k for k, v in sd.vars.items()
                 if v.var_type == "VARIABLE"
                 and np.asarray(sd.values[k]).ndim == 4)
    before = sd.values[probe].copy()
    ds = MultiDataSet([g["x"]], [np.asarray([0, 1], np.int32)])
    losses = sd.fit([ds], n_epochs=2)
    assert np.isfinite(losses).all(), losses
    assert not np.allclose(sd.values[probe], before)   # convs trained


def test_torch_exported_lstm_parity(tmp_path):
    """torch.nn.LSTM -> ONNX LSTM node -> import -> elementwise parity
    on all three outputs (y, h, c)."""
    torch.manual_seed(0)
    m = torch.nn.LSTM(input_size=4, hidden_size=6, num_layers=1)
    x = torch.randn(5, 2, 4)
    with torch.no_grad():
        y, (h, c) = m(x)
    p = str(tmp_path / "lstm.onnx")
    _export(m, (x,), p, input_names=["x"],
            output_names=["y", "h", "c"])
    sd = import_onnx(p)
    got = sd.output({"x": x.numpy()}, ["y", "h", "c"])
    np.testing.assert_allclose(np.asarray(got["y"]), y.numpy(),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got["h"]), h.numpy(),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got["c"]), c.numpy(),
                               atol=1e-5)


def test_torch_exported_bilstm_parity(tmp_path):
    torch.manual_seed(1)
    m = torch.nn.LSTM(input_size=3, hidden_size=4, num_layers=1,
                      bidirectional=True)
    x = torch.randn(6, 2, 3)
    with torch.no_grad():
        y, _ = m(x)
    p = str(tmp_path / "bilstm.onnx")
    _export(m, (x,), p, input_names=["x"],
            output_names=["y", "h", "c"])
    sd = import_onnx(p)
    got = np.asarray(sd.output({"x": x.numpy()}, ["y"])["y"])
    np.testing.assert_allclose(got, y.numpy(), atol=1e-5)


def test_torch_exported_gru_parity(tmp_path):
    torch.manual_seed(2)
    m = torch.nn.GRU(input_size=4, hidden_size=5, num_layers=1)
    x = torch.randn(5, 2, 4)
    with torch.no_grad():
        y, h = m(x)
    p = str(tmp_path / "gru.onnx")
    _export(m, (x,), p, input_names=["x"], output_names=["y", "h"])
    sd = import_onnx(p)
    got = sd.output({"x": x.numpy()}, ["y", "h"])
    np.testing.assert_allclose(np.asarray(got["y"]), y.numpy(),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got["h"]), h.numpy(),
                               atol=1e-5)


def test_torch_exported_lstm_finetunes(tmp_path):
    """Gradients flow through the imported ONNX LSTM scan."""
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.optimize.updaters import Sgd
    torch.manual_seed(3)
    m = torch.nn.LSTM(input_size=3, hidden_size=4, num_layers=1)
    x = torch.randn(5, 4, 3)
    p = str(tmp_path / "lstm_ft.onnx")
    _export(m, (x,), p, input_names=["x"],
            output_names=["y", "h", "c"])
    sd = import_onnx(p)
    tgt = sd.placeholder("tgt", (None, None, 4), "float32")
    d = sd.op("sub", sd.vars["y"], tgt)
    sd.set_loss_variables(sd.reduce_mean(sd.op("square", d),
                                         name="loss"))
    sd.set_training_config(TrainingConfig(
        updater=Sgd(learning_rate=0.1),
        data_set_feature_mapping=["x"], data_set_label_mapping=["tgt"]))
    kern = next(k for k, v in sd.vars.items()
                if v.var_type == "VARIABLE"
                and np.asarray(sd.values[k]).ndim == 3
                and np.asarray(sd.values[k]).shape[-1] == 3)
    before = sd.values[kern].copy()
    rng = np.random.default_rng(0)
    ds = MultiDataSet([x.numpy()],
                      [rng.normal(size=(5, 4, 4)).astype(np.float32)])
    losses = sd.fit([ds] * 15, n_epochs=1)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
    assert not np.allclose(sd.values[kern], before)


def test_torch_exported_lstm_pruned_outputs(tmp_path):
    """Review regression: a module returning ONLY y prunes the ONNX
    LSTM node to one declared output — position binding must hold."""
    torch.manual_seed(4)

    class OnlyY(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.lstm = torch.nn.LSTM(3, 4)

        def forward(self, x):
            y, _ = self.lstm(x)
            return y.sum(dim=2)

    m = OnlyY()
    x = torch.randn(5, 2, 3)
    with torch.no_grad():
        expected = m(x).numpy()
    p = str(tmp_path / "onlyy.onnx")
    _export(m, (x,), p, input_names=["x"], output_names=["out"])
    sd = import_onnx(p)
    got = np.asarray(sd.output({"x": x.numpy()}, ["out"])["out"])
    np.testing.assert_allclose(got, expected, atol=1e-5)


def test_expand_target_shorter_than_input_rank():
    """Review regression: ONNX Expand's bidirectional broadcast with a
    target of LOWER rank than x must keep x's rank."""
    from deeplearning4j_tpu.autodiff.ops import get_op
    x = np.ones((2, 3), np.float32)
    out = get_op("broadcast_to_dynamic").fn(x, np.asarray([3]))
    assert np.shape(out) == (2, 3)
    out2 = get_op("broadcast_to_dynamic").fn(
        np.ones((1, 3), np.float32), np.asarray([4, 2, 3]))
    assert np.shape(out2) == (4, 2, 3)
