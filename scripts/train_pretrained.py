#!/usr/bin/env python
"""Train + publish the in-repo pretrained weight sets
(``zoo/weights/*.zip`` + sha256 manifests) — the stand-in for
upstream's blob-hosted ``ZooModel.pretrainedUrl`` table (no egress in
this environment; the synthetic-MNIST caveat from ``data/mnist.py``
applies to the reported accuracies).

Run from the repo root:  python scripts/train_pretrained.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


class ImageMnist:
    """Flat [b, 784] MNIST reshaped to NHWC images for conv models."""

    def __init__(self, inner):
        self.inner = inner

    def __iter__(self):
        from deeplearning4j_tpu.data.dataset import DataSet
        for ds in self.inner:
            yield DataSet(
                np.asarray(ds.features).reshape(-1, 28, 28, 1),
                ds.labels)

    def reset(self):
        self.inner.reset()


def train_lenet(out_dir):
    from deeplearning4j_tpu.data.mnist import MnistDataSetIterator
    from deeplearning4j_tpu.zoo import LeNet, save_pretrained
    from deeplearning4j_tpu.optimize.updaters import Adam

    model = LeNet(n_classes=10, input_shape=(28, 28, 1), seed=12,
                  updater=Adam(learning_rate=1e-3)).init_graph()
    train = ImageMnist(MnistDataSetIterator(128, n_examples=20000))
    model.fit(train, n_epochs=4)
    test = ImageMnist(MnistDataSetIterator(256, n_examples=5000,
                                           train=False))
    acc = model.evaluate(test).accuracy()
    print(f"LeNet synthetic-MNIST test acc: {acc:.4f}")
    assert acc > 0.97, acc
    entry = save_pretrained(model, "LeNet", "mnist", out_dir)
    print("published:", entry)


def train_char_rnn(out_dir):
    from deeplearning4j_tpu.data.char_iterator import (
        CharacterIterator, sample_characters)
    from deeplearning4j_tpu.zoo import TextGenerationLSTM, save_pretrained

    text = ("the quick brown fox jumps over the lazy dog. "
            "pack my box with five dozen liquor jugs. " * 60)
    it = CharacterIterator(text, seq_length=40, batch=16, seed=3)
    model = TextGenerationLSTM(vocab_size=it.vocab_size, hidden=96,
                               n_layers=1, tbptt_length=20,
                               seed=7).init_graph()
    first = model.fit(it, n_epochs=1, async_prefetch=False)
    last = first
    for _ in range(24):
        last = model.fit(it, n_epochs=1, async_prefetch=False)
    print(f"char-RNN loss {first:.3f} -> {last:.3f}")
    assert last < first * 0.5, (first, last)
    sample = sample_characters(model, it, init="the ", n_chars=60,
                               temperature=0.3)
    print("sample:", repr(sample))
    entry = save_pretrained(model, "TextGenerationLSTM", "pangrams",
                            out_dir)
    # the sampler needs the char vocabulary — store it in the manifest
    import json
    mpath = entry["path"] + ".json"
    with open(mpath) as f:
        m = json.load(f)
    m["vocab"] = it.chars if isinstance(it.chars, str) else \
        "".join(it.chars)
    m["sha256"] = entry["sha256"]
    with open(mpath, "w") as f:
        json.dump(m, f)
    print("published:", entry)


def train_simple_cnn(out_dir):
    """SimpleCNN on (synthetic, see data/builtin.py) CIFAR-10 — the
    conv-net-at-CIFAR-scale registry entry (round-3 review item 9)."""
    from deeplearning4j_tpu.data.builtin import Cifar10DataSetIterator
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.zoo import SimpleCNN, save_pretrained

    model = SimpleCNN(n_classes=10, input_shape=(32, 32, 3), seed=4,
                      updater=Adam(learning_rate=1e-3)).init_graph()
    train = Cifar10DataSetIterator(128, n_examples=8000, seed=11)
    model.fit(train, n_epochs=3)
    test = Cifar10DataSetIterator(256, train=False, n_examples=2000,
                                  seed=11)
    acc = model.evaluate(test).accuracy()
    print(f"SimpleCNN synthetic-CIFAR test acc: {acc:.4f}")
    assert acc > 0.9, acc
    entry = save_pretrained(model, "SimpleCNN", "cifar10-synthetic",
                            out_dir)
    print("published:", entry)


def train_gpt_char(out_dir):
    """Small causal char-LM via zoo.Gpt + KV-cache sampling — the
    transformer registry entry (round-3 review item 9)."""
    import json

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.models.generation import TransformerGenerator
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.zoo import save_pretrained
    from deeplearning4j_tpu.zoo.gpt import Gpt

    text = ("the quick brown fox jumps over the lazy dog. "
            "pack my box with five dozen liquor jugs. " * 40)
    chars = sorted(set(text))
    c2i = {c: i for i, c in enumerate(chars)}
    ids = np.asarray([c2i[c] for c in text], np.int32)
    t = 40
    starts = np.arange(0, len(ids) - t - 1, 7)
    xs = np.stack([ids[s:s + t] for s in starts])
    ys = np.stack([ids[s + 1:s + t + 1] for s in starts])

    model = Gpt(vocab_size=len(chars), max_len=64, d_model=64,
                n_layers=2, n_heads=4, d_ff=128, seq_len=t,
                compute_dtype=None, seed=9,
                updater=Adam(learning_rate=3e-3)).init_graph()
    rng = np.random.default_rng(0)
    first = last = None
    for epoch in range(30):
        order = rng.permutation(len(xs))
        for i in range(0, len(order), 32):
            b = order[i:i + 32]
            last = model.fit(DataSet(xs[b], ys[b]))
            if first is None:
                first = last
    print(f"char-GPT loss {first:.3f} -> {last:.3f}")
    assert last < 0.5 * first, (first, last)

    gen = TransformerGenerator(model)
    prompt = np.asarray([[c2i[c] for c in "the "]], np.int32)
    out = gen.generate(prompt, n_new=24)
    sample = "".join(chars[i] for i in out[0])
    print("sample:", repr(sample))

    entry = save_pretrained(model, "Gpt", "pangrams-char", out_dir)
    mpath = entry["path"] + ".json"
    with open(mpath) as f:
        m = json.load(f)
    m["vocab"] = "".join(chars)
    with open(mpath, "w") as f:
        json.dump(m, f)
    print("published:", entry)


def main():
    from deeplearning4j_tpu.zoo.pretrained import package_weights_dir
    out = package_weights_dir()
    os.makedirs(out, exist_ok=True)
    train_lenet(out)
    train_char_rnn(out)
    train_simple_cnn(out)
    train_gpt_char(out)


if __name__ == "__main__":
    main()
