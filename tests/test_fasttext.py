"""FastText subword embeddings (round-2 review missing item 7): n-gram
hashing, subword-composed vectors, OOV handling, training quality."""
import numpy as np
import pytest

from deeplearning4j_tpu.nlp import FastText
from deeplearning4j_tpu.nlp.fasttext import fnv1a, word_ngrams


def test_fnv1a_known_values():
    # FNV-1a 32-bit reference values
    assert fnv1a("") == 2166136261
    assert fnv1a("a") == 0xE40C292C
    assert fnv1a("foobar") == 0xBF9CF968


def test_word_ngrams_wrapping_and_range():
    grams = word_ngrams("cat", 3, 4)
    # "<cat>" -> 3-grams: <ca cat at> ; 4-grams: <cat cat>
    assert "<ca" in grams and "cat" in grams and "at>" in grams
    assert "<cat" in grams and "cat>" in grams
    assert "<cat>" not in grams          # full token excluded
    assert word_ngrams("ab", 3, 3) == ["<ab", "ab>"]


def _corpus(rng, n=250):
    a = [f"apple{i}" for i in range(8)]
    b = [f"boat{i}" for i in range(8)]
    sents = [" ".join(rng.choice(a if rng.random() < 0.5 else b, 6))
             for _ in range(n)]
    return sents, a, b


@pytest.fixture(scope="module")
def trained():
    rng = np.random.default_rng(0)
    sents, a, b = _corpus(rng)
    m = FastText(vector_size=24, window_size=3, epochs=8,
                 batch_size=128, learning_rate=0.8, seed=1, bucket=5000)
    losses = m.fit(sents)
    return m, a, b, losses


def test_fasttext_trains_and_ranks_topics(trained):
    m, a, b, losses = trained
    assert losses[-1] < losses[0] * 0.8
    intra = np.mean([m.similarity(a[i], a[i + 1]) for i in range(0, 6, 2)])
    inter = np.mean([m.similarity(a[i], b[i]) for i in range(0, 6, 2)])
    assert intra > inter
    assert all(w.startswith("apple") for w in m.words_nearest("apple0", 3))


def test_fasttext_oov_vectors(trained):
    """The FastText hallmark: unseen words get subword-composed
    vectors ranked toward their morphological family."""
    m, a, b, _ = trained
    assert m.has_word("never_seen_token")
    v = m.get_word_vector("apple999")      # OOV
    assert v.shape == (24,)
    assert np.isfinite(v).all()
    assert m.similarity("apple999", "apple0") > \
        m.similarity("apple999", "boat0")


def test_fasttext_rejects_hs():
    with pytest.raises(NotImplementedError, match="negative sampling"):
        FastText(use_hierarchic_softmax=True).fit(["a b c d e"])
