"""In-repo published pretrained weights (round-2 review item 7): the
``initPretrained`` parity path exercised against REAL weight files
(``zoo/weights/``, trained by ``scripts/train_pretrained.py``)."""
import json
import os

import numpy as np
import pytest

from deeplearning4j_tpu.zoo import load_pretrained
from deeplearning4j_tpu.zoo.pretrained import package_weights_dir

WEIGHTS = package_weights_dir()


def test_published_weight_sets_exist_with_manifests():
    names = {"LeNet_mnist", "TextGenerationLSTM_pangrams"}
    for n in names:
        zips = os.path.join(WEIGHTS, n + ".zip")
        assert os.path.exists(zips), zips
        with open(zips + ".json") as f:
            m = json.load(f)
        assert m["sha256"]


def test_lenet_pretrained_restores_and_evaluates():
    """load_pretrained -> evaluate: the published LeNet must still
    score >0.97 on the (synthetic — see data/mnist.py) test split."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.mnist import MnistDataSetIterator
    model = load_pretrained("LeNet", "mnist")
    it = MnistDataSetIterator(256, n_examples=2000, train=False)
    correct = total = 0
    for ds in it:
        x = np.asarray(ds.features).reshape(-1, 28, 28, 1)
        pred = np.asarray(model.output(x)).argmax(-1)
        correct += int((pred == np.asarray(ds.labels).argmax(-1)).sum())
        total += len(pred)
    assert correct / total > 0.97, correct / total


def test_char_rnn_pretrained_generates():
    from deeplearning4j_tpu.data.char_iterator import (
        CharacterIterator, sample_characters)
    model = load_pretrained("TextGenerationLSTM", "pangrams")
    with open(os.path.join(
            WEIGHTS, "TextGenerationLSTM_pangrams.zip.json")) as f:
        vocab = json.load(f)["vocab"]
    it = CharacterIterator("".join(vocab), seq_length=10, batch=1,
                           valid_chars=vocab)
    out = sample_characters(model, it, init="the ", n_chars=40,
                            temperature=0.3)
    assert len(out) == 44
    # a trained pangram model keeps emitting in-vocab words
    assert any(w in out for w in ("the", "fox", "dog", "box", "quick",
                                  "jugs", "lazy")), out


def test_checksum_tamper_detection(tmp_path):
    """Corrupted published weights must be refused (upstream
    checkSumForPretrained contract)."""
    import shutil
    d = str(tmp_path)
    for ext in (".zip", ".zip.json"):
        shutil.copy(os.path.join(WEIGHTS, "LeNet_mnist" + ext),
                    os.path.join(d, "LeNet_mnist" + ext))
    with open(os.path.join(d, "LeNet_mnist.zip"), "r+b") as f:
        f.seek(100)
        f.write(b"\x00\x01\x02\x03")
    with pytest.raises(IOError, match="Checksum mismatch"):
        load_pretrained("LeNet", "mnist", directory=d)


def test_simple_cnn_pretrained_restores_and_evaluates():
    """Round-4 registry entry: published SimpleCNN scores >0.9 on the
    (synthetic — see data/builtin.py) CIFAR test split."""
    from deeplearning4j_tpu.data.builtin import Cifar10DataSetIterator
    model = load_pretrained("SimpleCNN", "cifar10-synthetic")
    it = Cifar10DataSetIterator(256, train=False, n_examples=1000,
                                seed=11)
    correct = total = 0
    for ds in it:
        pred = np.asarray(model.output(np.asarray(ds.features))).argmax(-1)
        correct += int((pred == np.asarray(ds.labels).argmax(-1)).sum())
        total += len(pred)
    assert correct / total > 0.9, correct / total


def test_gpt_pretrained_generates_with_kv_cache():
    """Round-4 registry entry: the published causal char-LM generates
    coherent pangram text through the KV-cache decoder."""
    from deeplearning4j_tpu.models.generation import TransformerGenerator
    model = load_pretrained("Gpt", "pangrams-char")
    with open(os.path.join(WEIGHTS, "Gpt_pangrams-char.zip.json")) as f:
        vocab = json.load(f)["vocab"]
    c2i = {c: i for i, c in enumerate(vocab)}
    gen = TransformerGenerator(model)
    prompt = np.asarray([[c2i[c] for c in "the "]], np.int32)
    out = gen.generate(prompt, n_new=24)
    text = "".join(vocab[i] for i in out[0])
    assert text.startswith("the ")
    assert any(w in text for w in ("quick", "brown", "fox", "jumps",
                                   "dog", "box")), text


def test_registry_has_at_least_four_real_entries():
    import glob
    zips = glob.glob(os.path.join(WEIGHTS, "*.zip"))
    assert len(zips) >= 4, zips
