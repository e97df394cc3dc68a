"""The config-4 quality pipeline at CPU scale (round-4 review item 3):
hand-written sentiment corpus -> WordPiece -> BertIterator ->
imported-frozen-BERT fine-tune -> held-out accuracy above chance.
The TPU artifact (FINETUNE_r05.json, scripts/bench_imported_finetune)
runs the same pipeline on BERT-base at b=40/t=512; this test proves
the LEARNING claim end to end on the tiny frozen fixture (t=16 — the
corpus's longest sentence encodes to exactly 16 tokens)."""
import os

import numpy as np
import pytest

from deeplearning4j_tpu.data.bert_iterator import BertIterator
from deeplearning4j_tpu.data.tiny_sentiment import (load_tiny_sentiment,
                                                    make_tokenizer,
                                                    train_test_split)

FIX = os.path.join(os.path.dirname(__file__), "fixtures")


def test_corpus_integrity():
    data = load_tiny_sentiment()
    assert len(data) == 318
    labels = [l for _, l in data]
    assert sum(labels) == 159                      # balanced
    texts = [t for t, _ in data]
    assert len(set(texts)) == len(texts)           # no duplicates
    train, test = train_test_split()
    assert len(train) == 238 and len(test) == 80
    assert not set(t for t, _ in train) & set(t for t, _ in test)
    assert 30 <= sum(l for _, l in test) <= 50     # held-out balanced-ish


def test_vocab_covers_corpus_no_unk():
    tok = make_tokenizer()
    unk = tok.vocab["[UNK]"]
    for text, _ in load_tiny_sentiment():
        ids, mask, _ = tok.encode(text, max_len=16)
        assert unk not in ids
        assert sum(mask) >= 4                      # CLS + words + SEP


def test_imported_bert_learns_held_out_sentiment():
    """The claim the artifact rests on: training on REAL labeled text
    lifts HELD-OUT accuracy materially above chance — generalization,
    not memorization (train/test sentences are disjoint)."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.autodiff.tf_import import import_frozen_pb
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.utils.bert_fixture import attach_classifier_head

    sd = import_frozen_pb(os.path.join(FIX, "bert_tiny_sentiment_frozen.pb"))
    attach_classifier_head(sd)
    sd.set_training_config(TrainingConfig(
        updater=Adam(learning_rate=3e-4),
        data_set_feature_mapping=["i", "m", "t"],
        data_set_label_mapping=["labels"]))

    tok = make_tokenizer()
    train, test = train_test_split()
    np.random.default_rng(7).shuffle(train)    # mix labels per batch
    batch, t = 34, 16                    # 238 = 7 x 34, shape-stable
    train_it = list(BertIterator(tok, train, batch, t))
    test_it = list(BertIterator(tok, test, 40, t))

    logits_fn = sd._function(["logits"], ["i", "m", "t"])

    def acc(params):
        hits = total = 0
        for mds in test_it:
            ids, mask, tt = mds.features
            lg = logits_fn(params, {"i": jnp.asarray(ids),
                                    "m": jnp.asarray(mask),
                                    "t": jnp.asarray(tt)})[0]
            hits += int(jnp.sum(jnp.argmax(lg, -1)
                                == jnp.asarray(mds.labels[0])))
            total += len(mds.labels[0])
        return hits / total

    params0 = {k: jnp.asarray(v) for k, v in sd._param_values().items()}
    before = acc(params0)

    losses = sd.fit(train_it, n_epochs=25)
    params1 = {k: jnp.asarray(v) for k, v in sd._param_values().items()}
    after = acc(params1)

    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    # random init hovers at chance; the lexical task generalizes
    # (measured: 0.725 at ep15, 0.738 at ep30 on this 2x64 model)
    assert after >= 0.70, (before, after)
    assert after > before + 0.15, (before, after)
