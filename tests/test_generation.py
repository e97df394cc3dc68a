"""KV-cache incremental decoding (round-3 review item 2): the transformer
``rnnTimeStep`` analogue.  Greedy decode through the cached one-step
path must EXACTLY match greedy decode by full-prefix recompute."""
import numpy as np
import pytest

from deeplearning4j_tpu.models.generation import TransformerGenerator
from deeplearning4j_tpu.zoo.gpt import Gpt


def _tiny_gpt(**kw):
    cfg = dict(vocab_size=50, max_len=32, d_model=32, n_layers=2,
               n_heads=4, d_ff=64, seq_len=8, compute_dtype=None,
               seed=3)
    cfg.update(kw)
    return Gpt(**cfg).init_graph()


def test_cached_greedy_matches_full_recompute():
    net = _tiny_gpt()
    gen = TransformerGenerator(net)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 50, (2, 4)).astype(np.int32)
    t0, n_new = prompt.shape[1], 6

    got = gen.generate(prompt, n_new=n_new)
    assert got.shape == (2, t0 + n_new)
    np.testing.assert_array_equal(got[:, :t0], prompt)

    # reference: recompute the FULL prefix every step (no cache)
    ids = prompt.copy()
    for _ in range(n_new):
        probs = np.asarray(net.output(ids))        # [b, t, v]
        nxt = probs[:, -1].argmax(-1).astype(np.int32)
        ids = np.concatenate([ids, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(got, ids)


def test_cached_logits_match_full_forward():
    """Numerical check under the argmax: per-step cached logits equal
    the full forward's last-position distribution."""
    net = _tiny_gpt()
    gen = TransformerGenerator(net)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 50, (1, 5)).astype(np.int32)
    import jax.numpy as jnp
    emb_p, blk_ps, head_p = gen._params()
    blk_stack = gen._stack_blocks(blk_ps)
    kc = jnp.zeros((len(gen.blocks), 1, 4, 8, 8))
    vc = jnp.zeros((len(gen.blocks), 1, 4, 8, 8))
    logits = None
    for pos in range(prompt.shape[1]):
        logits, kc, vc, rec = gen._step(emb_p, blk_stack, head_p, kc, vc,
                                        None, jnp.asarray(prompt[:, pos]),
                                        pos)
        assert rec is None
    import jax
    full_probs = np.asarray(net.output(prompt))[:, -1]
    step_probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    np.testing.assert_allclose(step_probs, full_probs, atol=1e-5)


def test_sampling_temperature_and_shapes():
    net = _tiny_gpt()
    gen = TransformerGenerator(net)
    prompt = np.asarray([[1, 2, 3]], np.int32)
    a = gen.generate(prompt, n_new=5, temperature=1.0, seed=0)
    b = gen.generate(prompt, n_new=5, temperature=1.0, seed=1)
    assert a.shape == b.shape == (1, 8)
    assert (a >= 0).all() and (a < 50).all()


def test_generator_rejects_non_causal():
    from deeplearning4j_tpu.zoo.bert import Bert
    net = Bert(vocab_size=50, max_len=16, d_model=32, n_layers=1,
               n_heads=4, d_ff=64, seq_len=8, compute_dtype=None,
               seed=0).init_graph()
    with pytest.raises(ValueError):
        TransformerGenerator(net)


def test_gpt_trains_sparse_labels():
    """The decoder trains with SPARSE [b, t] integer labels (no
    one-hot): loss finite and decreasing on a copy task."""
    from deeplearning4j_tpu.data.dataset import DataSet
    net = _tiny_gpt(seq_len=8)
    rng = np.random.default_rng(2)
    x = rng.integers(0, 50, (16, 8)).astype(np.int32)
    labels = np.roll(x, -1, axis=1).astype(np.int32)  # next-token
    ds = DataSet(x, labels)
    first = net.fit(ds)
    for _ in range(30):
        last = net.fit(ds)
    assert np.isfinite(last)
    assert last < first, (first, last)


def test_generate_rejects_beyond_positional_table():
    # ADVICE r4: past the table, dynamic_slice would clamp silently and
    # reuse the last positional row — must raise instead.
    net = _tiny_gpt()          # max_len=32 positional rows
    gen = TransformerGenerator(net)
    prompt = np.zeros((1, 4), np.int32)
    with pytest.raises(ValueError, match="positional table"):
        gen.generate(prompt, n_new=40)
    with pytest.raises(ValueError, match="positional table"):
        gen.generate(prompt, n_new=2, max_len=64)


def test_top_k_and_top_p_filtering():
    from deeplearning4j_tpu.models.generation import _filter_logits
    import jax.numpy as jnp
    lg = jnp.asarray([[1.0, 3.0, 2.0, -1.0]])
    k2 = np.asarray(_filter_logits(lg, 2, None))
    assert np.isneginf(k2[0, 0]) and np.isneginf(k2[0, 3])
    assert k2[0, 1] == 3.0 and k2[0, 2] == 2.0
    # nucleus: top token survives even with tiny p
    p_small = np.asarray(_filter_logits(lg, None, 1e-6))
    assert p_small[0, 1] == 3.0
    assert np.isneginf(p_small[0, [0, 2, 3]]).all()
    # p ~ 1 keeps everything
    p_all = np.asarray(_filter_logits(lg, None, 0.9999))
    assert np.isfinite(p_all).all()


def test_top_k_1_matches_greedy():
    net = _tiny_gpt()
    gen = TransformerGenerator(net)
    prompt = np.random.default_rng(5).integers(0, 50, (2, 4)).astype(
        np.int32)
    greedy = gen.generate(prompt, n_new=6)
    k1 = gen.generate(prompt, n_new=6, temperature=0.7, top_k=1)
    np.testing.assert_array_equal(greedy, k1)
    with pytest.raises(ValueError, match="temperature"):
        gen.generate(prompt, n_new=2, top_k=5)


def test_top_p_sampling_stays_in_nucleus():
    net = _tiny_gpt()
    gen = TransformerGenerator(net)
    prompt = np.random.default_rng(6).integers(0, 50, (2, 4)).astype(
        np.int32)
    out = gen.generate(prompt, n_new=8, temperature=1.0, top_p=0.9,
                       seed=1)
    assert out.shape == (2, 12)
    assert (out >= 0).all() and (out < 50).all()
    np.testing.assert_array_equal(out[:, :4], prompt)
