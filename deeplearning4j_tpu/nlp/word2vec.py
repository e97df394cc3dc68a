"""Word2Vec / ParagraphVectors — batched skip-gram negative sampling.

Parity surface (``org.deeplearning4j.models.word2vec.Word2Vec`` builder):
``vector_size`` (layerSize), ``window_size``, ``negative``,
``min_word_frequency``, ``iterations``/``epochs``, ``learning_rate``,
``seed``; API ``fit``, ``get_word_vector``, ``words_nearest``,
``similarity``, ``vocab``.

Training design (TPU-first, replacing the reference's threaded
lock-free SGD over a hierarchical-softmax tree): all (center, context)
pairs are materialized host-side per epoch, shuffled, and consumed by a
single jitted step that samples negatives with ``jax.random`` and
applies the NS gradient as one batched scatter-add — no locks, no
per-token kernel launches.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.analysis import sanitize as _sanitize
from deeplearning4j_tpu.nlp.tokenizer import DefaultTokenizerFactory


def build_huffman(counts: Sequence[int]):
    """Huffman tree over word counts (word2vec.c / DL4J
    ``useHierarchicSoftmax`` semantics): returns (points, codes, mask)
    arrays [n, D] — per-word inner-node path, binary code, and
    valid-depth mask, padded to the max depth D."""
    import heapq
    n = len(counts)
    if n < 2:
        raise ValueError("Huffman tree needs a vocabulary of >= 2 words")
    heap = [(int(c), i) for i, c in enumerate(counts)]
    heapq.heapify(heap)
    parent: Dict[int, int] = {}
    branch: Dict[int, int] = {}
    nxt = n
    while len(heap) > 1:
        c1, a = heapq.heappop(heap)
        c2, b = heapq.heappop(heap)
        parent[a], branch[a] = nxt, 0
        parent[b], branch[b] = nxt, 1
        heapq.heappush(heap, (c1 + c2, nxt))
        nxt += 1
    root = heap[0][1]
    paths, codes = [], []
    for w in range(n):
        p, cd, node = [], [], w
        while node != root:
            cd.append(branch[node])
            node = parent[node]
            p.append(node - n)        # inner-node id in [0, n-1)
        paths.append(p[::-1])
        codes.append(cd[::-1])
    depth = max(len(p) for p in paths)
    points = np.zeros((n, depth), np.int32)
    code_a = np.zeros((n, depth), np.float32)
    mask = np.zeros((n, depth), np.float32)
    for w in range(n):
        k = len(paths[w])
        points[w, :k] = paths[w]
        code_a[w, :k] = codes[w]
        mask[w, :k] = 1.0
    return points, code_a, mask


@dataclasses.dataclass
class Word2Vec:
    vector_size: int = 64
    window_size: int = 5
    negative: int = 5
    min_word_frequency: int = 1
    epochs: int = 1
    batch_size: int = 512
    learning_rate: float = 0.5
    min_learning_rate: float = 1e-3
    seed: int = 42
    tokenizer_factory: object = None
    # word2vec.c fidelity knobs (round-2 review item 8):
    negative_table_power: float = 0.75  # unigram^0.75 sampling; 0=uniform
    use_hierarchic_softmax: bool = False  # Huffman-tree HS instead of NS
    sampling: float = 0.0               # frequent-word subsample t (0=off)

    def __post_init__(self):
        self.tokenizer_factory = (self.tokenizer_factory
                                  or DefaultTokenizerFactory())
        self.vocab: Dict[str, int] = {}
        self.index2word: List[str] = []
        self.counts: Counter = Counter()
        self.syn0: Optional[np.ndarray] = None  # input embeddings
        self.syn1: Optional[np.ndarray] = None  # output embeddings

    # ------------------------------------------------------------------
    def _build_vocab(self, token_lists: List[List[str]]):
        self.counts = Counter(t for toks in token_lists for t in toks)
        words = sorted(w for w, c in self.counts.items()
                       if c >= self.min_word_frequency)
        self.index2word = words
        self.vocab = {w: i for i, w in enumerate(words)}

    def _keep_prob(self) -> Optional[np.ndarray]:
        """word2vec.c frequent-word subsampling: keep word w with prob
        (sqrt(f/t) + 1) * t/f where f is the corpus frequency."""
        if not self.sampling:
            return None
        total = sum(self.counts[w] for w in self.index2word)
        f = np.asarray([self.counts[w] / total for w in self.index2word])
        keep = (np.sqrt(f / self.sampling) + 1) * self.sampling / f
        return np.minimum(keep, 1.0)

    def _pairs(self, token_lists: List[List[str]], rng: np.random.Generator
               ) -> np.ndarray:
        """All in-window (center, context) id pairs, shuffled; frequent
        words are subsampled first when ``sampling`` is set."""
        keep = self._keep_prob()
        out = []
        for toks in token_lists:
            ids = [self.vocab[t] for t in toks if t in self.vocab]
            if keep is not None:
                ids = [i for i in ids if rng.random() < keep[i]]
            for i, c in enumerate(ids):
                lo = max(0, i - self.window_size)
                hi = min(len(ids), i + self.window_size + 1)
                for j in range(lo, hi):
                    if j != i:
                        out.append((c, ids[j]))
        pairs = np.asarray(out, np.int32)
        rng.shuffle(pairs)
        return pairs

    # ------------------------------------------------------------------
    def _unigram_cdf(self, n_vocab: int) -> Optional[jnp.ndarray]:
        """CDF of the unigram^power negative-sampling distribution
        (word2vec.c's table; DL4J builds the same 1e8-slot table —
        inverse-CDF via searchsorted needs no giant table on TPU).
        None => uniform (power == 0 or no counts available)."""
        if not self.negative_table_power or not self.counts:
            return None
        c = np.asarray([self.counts[w] for w in self.index2word],
                       np.float64) ** self.negative_table_power
        return jnp.asarray(np.cumsum(c) / c.sum(), jnp.float32)

    def _make_step(self, n_vocab: int):
        neg = self.negative
        cdf = self._unigram_cdf(n_vocab)

        def sample_negatives(key, b):
            if cdf is None:
                return jax.random.randint(key, (b, neg), 0, n_vocab)
            u = jax.random.uniform(key, (b, neg))
            return jnp.clip(jnp.searchsorted(cdf, u), 0, n_vocab - 1
                            ).astype(jnp.int32)

        def step(syn0, syn1, centers, contexts, lr, key):
            """One NS update on a pair batch; returns new (syn0, syn1,
            loss)."""
            b = centers.shape[0]
            negs = sample_negatives(key, b)
            v_c = syn0[centers]                      # [b, d]
            u_pos = syn1[contexts]                   # [b, d]
            u_neg = syn1[negs]                       # [b, neg, d]
            pos_score = jnp.sum(v_c * u_pos, -1)
            neg_score = jnp.einsum("bd,bnd->bn", v_c, u_neg)
            loss = -(jnp.mean(jax.nn.log_sigmoid(pos_score)) +
                     jnp.mean(jnp.sum(jax.nn.log_sigmoid(-neg_score), -1)))
            # Analytic NS gradients (cheaper than jax.grad through the
            # gathers, and identical math to the reference's updates):
            g_pos = jax.nn.sigmoid(pos_score) - 1.0          # [b]
            g_neg = jax.nn.sigmoid(neg_score)                # [b, neg]
            d_vc = g_pos[:, None] * u_pos + jnp.einsum(
                "bn,bnd->bd", g_neg, u_neg)
            d_upos = g_pos[:, None] * v_c
            d_uneg = g_neg[..., None] * v_c[:, None, :]
            # MEAN-scaled batch updates: word2vec.c applies per-pair
            # sequential SGD, but a batched scatter-add of hundreds of
            # stale per-pair gradients diverges on small vocabularies;
            # the mean keeps the step size batch-size-invariant (the
            # default learning_rate is tuned for this regime).
            syn0 = syn0.at[centers].add(-lr * d_vc / b)
            syn1 = syn1.at[contexts].add(-lr * d_upos / b)
            syn1 = syn1.at[negs.reshape(-1)].add(
                -lr * d_uneg.reshape(-1, d_uneg.shape[-1]) / b)
            return syn0, syn1, loss

        return jax.jit(step, donate_argnums=(0, 1))

    def _make_hs_step(self, n_vocab: int):
        """Hierarchical-softmax step (``useHierarchicSoftmax``): the
        context word's Huffman path replaces negative samples; syn1
        holds the n_vocab-1 inner-node vectors."""
        counts = [self.counts[w] for w in self.index2word]
        points_h, codes_h, mask_h = build_huffman(counts)
        points_a = jnp.asarray(points_h)
        codes_a = jnp.asarray(codes_h)
        mask_a = jnp.asarray(mask_h)

        def step(syn0, syn1, centers, contexts, lr, key):
            b = centers.shape[0]
            pts = points_a[contexts]             # [b, D]
            cds = codes_a[contexts]              # [b, D]
            msk = mask_a[contexts]               # [b, D]
            v_c = syn0[centers]                  # [b, d]
            u = syn1[pts]                        # [b, D, d]
            score = jnp.einsum("bd,bkd->bk", v_c, u)
            sgn = 1.0 - 2.0 * cds                # code 0 -> +1, 1 -> -1
            loss = -jnp.sum(
                jax.nn.log_sigmoid(sgn * score) * msk) / b
            # word2vec.c HS gradient: g = (sigmoid(score) - (1 - code))
            g = (jax.nn.sigmoid(score) - (1.0 - cds)) * msk
            d_vc = jnp.einsum("bk,bkd->bd", g, u)
            d_u = g[..., None] * v_c[:, None, :]
            syn0 = syn0.at[centers].add(-lr * d_vc / b)
            syn1 = syn1.at[pts.reshape(-1)].add(
                -lr * d_u.reshape(-1, d_u.shape[-1]) / b)
            return syn0, syn1, loss

        return jax.jit(step, donate_argnums=(0, 1))

    def _train_pairs(self, pairs_all: np.ndarray, n_vocab: int,
                     n_rows: int, rng: np.random.Generator):
        """The shared SGD loop (NS or HS): epochs x shuffled batches
        with linear LR decay.  ``n_rows`` sizes syn0 (== n_vocab for
        Word2Vec; + n_docs for ParagraphVectors).  Returns (syn0, syn1,
        losses)."""
        d = self.vector_size
        syn0 = jnp.asarray(
            (rng.random((n_rows, d)) - 0.5) / d, jnp.float32)
        if self.use_hierarchic_softmax:
            syn1 = jnp.zeros((max(n_vocab - 1, 1), d), jnp.float32)
            step = self._make_hs_step(n_vocab)
        else:
            syn1 = jnp.zeros((n_vocab, d), jnp.float32)
            step = self._make_step(n_vocab)
        key = jax.random.key(self.seed)
        losses: List[float] = []
        n_batches_total = max(
            1, self.epochs * ((len(pairs_all) + self.batch_size - 1)
                              // self.batch_size))
        t = 0
        for _ in range(self.epochs):
            rng.shuffle(pairs_all)
            for k in range(0, len(pairs_all), self.batch_size):
                batch = pairs_all[k:k + self.batch_size]
                if len(batch) < 2:
                    continue
                # linear LR decay, as upstream
                lr = max(self.min_learning_rate,
                         self.learning_rate * (1 - t / n_batches_total))
                key, sub = jax.random.split(key)
                # donation discipline (DL4J_TPU_SANITIZE=donation): the
                # step donates syn0/syn1 in place — ledger-check, mark
                # BEFORE the dispatch (a host-side weakref record, not
                # a read — JIT105), then rebind to the outputs (shared
                # by Word2Vec NS/HS and the FastText subword step)
                _sanitize.check_not_donated("nlp/sgd_step", syn0, syn1)
                _sanitize.mark_donated("nlp/sgd_step", syn0, syn1)
                syn0, syn1, loss = step(
                    syn0, syn1, jnp.asarray(batch[:, 0]),
                    jnp.asarray(batch[:, 1]), jnp.asarray(lr, jnp.float32),
                    sub)
                losses.append(float(loss))
                t += 1
        return np.asarray(syn0), np.asarray(syn1), losses

    def fit(self, sentences: Sequence[str]) -> List[float]:
        token_lists = [self.tokenizer_factory.tokenize(s)
                       for s in sentences]
        self._build_vocab(token_lists)
        n_vocab = len(self.vocab)
        if n_vocab == 0:
            raise ValueError("Empty vocabulary (check min_word_frequency)")
        rng = np.random.default_rng(self.seed)
        pairs_all = self._pairs(token_lists, rng)
        self.syn0, self.syn1, losses = self._train_pairs(
            pairs_all, n_vocab, n_vocab, rng)
        return losses

    # ------------------------------------------------------------------
    def has_word(self, w: str) -> bool:
        return w in self.vocab

    def get_word_vector(self, w: str) -> np.ndarray:
        return self.syn0[self.vocab[w]]

    def similarity(self, a: str, b: str) -> float:
        va, vb = self.get_word_vector(a), self.get_word_vector(b)
        return float(va @ vb / (np.linalg.norm(va) * np.linalg.norm(vb)
                                + 1e-12))

    def words_nearest(self, w: str, n: int = 10) -> List[str]:
        v = self.get_word_vector(w)
        norms = np.linalg.norm(self.syn0, axis=1) + 1e-12
        sims = self.syn0 @ v / (norms * (np.linalg.norm(v) + 1e-12))
        order = np.argsort(-sims)
        out = [self.index2word[i] for i in order
               if self.index2word[i] != w]
        return out[:n]


@dataclasses.dataclass
class ParagraphVectors(Word2Vec):
    """PV-DBOW (``ParagraphVectors`` with dm=0): a learned vector per
    document predicts the document's words with the same NS loss; word
    vectors co-train as in Word2Vec."""

    def __post_init__(self):
        super().__post_init__()
        self.doc_vectors: Optional[np.ndarray] = None

    def fit(self, documents: Sequence[str]) -> List[float]:
        token_lists = [self.tokenizer_factory.tokenize(s)
                       for s in documents]
        self._build_vocab(token_lists)
        n_vocab, n_docs = len(self.vocab), len(documents)
        rng = np.random.default_rng(self.seed)
        # Doc ids live in the same embedding table after the words, so
        # (doc_id + n_vocab, word) pairs reuse the word2vec step; the
        # word-window pairs are ALSO included so word vectors co-train
        # (DL4J trainWordVectors=true default — doc-only pairs would
        # leave syn0's word rows at their random init).
        doc_pairs = [(n_vocab + di, self.vocab[t])
                     for di, toks in enumerate(token_lists)
                     for t in toks if t in self.vocab]
        word_pairs = self._pairs(token_lists, rng)
        pairs_all = np.concatenate(
            [word_pairs.reshape(-1, 2),
             np.asarray(doc_pairs, np.int32).reshape(-1, 2)])
        full, self.syn1, losses = self._train_pairs(
            pairs_all, n_vocab, n_vocab + n_docs, rng)
        self.syn0 = full[:n_vocab]
        self.doc_vectors = full[n_vocab:]
        return losses

    def get_doc_vector(self, i: int) -> np.ndarray:
        return self.doc_vectors[i]
