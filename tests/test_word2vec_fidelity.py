"""Word2Vec fidelity (round-2 review item 8): unigram^0.75 negative
sampling, Huffman hierarchical softmax, frequent-word subsampling, and
an embedding-quality assertion on a corpus with known co-occurrence
structure."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nlp.word2vec import Word2Vec, build_huffman


def _topic_corpus(rng, n_sent=300, sent_len=8):
    """Two disjoint topics: words co-occur only within their topic."""
    a = [f"apple{i}" for i in range(10)]
    b = [f"boat{i}" for i in range(10)]
    sents = []
    for _ in range(n_sent):
        pool = a if rng.random() < 0.5 else b
        sents.append(" ".join(rng.choice(pool, sent_len)))
    return sents, a, b


def _quality(model, a, b):
    intra, inter = [], []
    for i in range(0, 8, 2):
        intra.append(model.similarity(a[i], a[i + 1]))
        intra.append(model.similarity(b[i], b[i + 1]))
        inter.append(model.similarity(a[i], b[i]))
    return float(np.mean(intra)), float(np.mean(inter))


def test_huffman_tree_properties():
    counts = [100, 50, 20, 10, 5, 2, 1]
    points, codes, mask = build_huffman(counts)
    n = len(counts)
    assert points.shape == codes.shape == mask.shape
    depths = mask.sum(1).astype(int)
    # frequent words get shorter codes
    assert depths[0] == depths.min()
    assert depths[-1] == depths.max()
    # prefix-free: all (code, depth) pairs distinct as full codes
    full = {tuple(codes[w, :depths[w]]) for w in range(n)}
    assert len(full) == n
    # inner-node ids within [0, n-1)
    assert points[mask > 0].max() < n - 1
    assert points[mask > 0].min() >= 0


def test_huffman_rejects_tiny_vocab():
    with pytest.raises(ValueError, match=">= 2"):
        build_huffman([5])


def test_unigram_power_sampling_distribution():
    """Negative samples must follow counts^0.75, not uniform."""
    m = Word2Vec(vector_size=8)
    m.index2word = ["common", "mid", "rare"]
    m.vocab = {w: i for i, w in enumerate(m.index2word)}
    from collections import Counter
    m.counts = Counter({"common": 1000, "mid": 100, "rare": 10})
    cdf = m._unigram_cdf(3)
    u = jax.random.uniform(jax.random.key(0), (50000,))
    samples = np.asarray(jnp.searchsorted(cdf, u))
    freq = np.bincount(samples, minlength=3) / len(samples)
    expect = np.array([1000.0, 100.0, 10.0]) ** 0.75
    expect = expect / expect.sum()
    np.testing.assert_allclose(freq, expect, atol=0.01)
    # power=0 => uniform (legacy behavior available)
    m.negative_table_power = 0.0
    assert m._unigram_cdf(3) is None


def test_subsampling_keep_probabilities():
    m = Word2Vec(sampling=1e-2)
    m.index2word = ["the", "rare"]
    from collections import Counter
    m.counts = Counter({"the": 990, "rare": 10})
    keep = m._keep_prob()
    assert keep[1] == 1.0                 # rare words always kept
    assert keep[0] < 0.5                  # stopword heavily dropped
    m2 = Word2Vec(sampling=0.0)
    assert m2._keep_prob() is None


def test_ns_unigram_embedding_quality():
    rng = np.random.default_rng(0)
    sents, a, b = _topic_corpus(rng)
    m = Word2Vec(vector_size=24, window_size=3, negative=5, epochs=10,
                 batch_size=128, learning_rate=1.0, seed=1)
    losses = m.fit(sents)
    assert losses[-1] < losses[0] * 0.6
    intra, inter = _quality(m, a, b)
    assert intra > inter + 0.3, (intra, inter)


def test_hs_embedding_quality():
    """Hierarchical softmax trains embeddings with the same topical
    structure — no negative sampling involved."""
    rng = np.random.default_rng(1)
    sents, a, b = _topic_corpus(rng)
    m = Word2Vec(vector_size=24, window_size=3, epochs=10,
                 batch_size=128, learning_rate=1.0, seed=2,
                 use_hierarchic_softmax=True)
    losses = m.fit(sents)
    assert losses[-1] < losses[0] * 0.85
    intra, inter = _quality(m, a, b)
    assert intra > inter + 0.3, (intra, inter)


def test_sampling_end_to_end():
    rng = np.random.default_rng(2)
    sents, a, b = _topic_corpus(rng)
    m = Word2Vec(vector_size=16, window_size=3, epochs=2, seed=3,
                 sampling=1e-2)
    losses = m.fit(sents)
    assert np.isfinite(losses).all()
    assert m.has_word(a[0])
