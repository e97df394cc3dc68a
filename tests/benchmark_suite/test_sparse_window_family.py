"""The sparse-expert family with window and full attention mixed
(``benchmark/families/sparse_window.py``) and the program it drives, at
a tiny size on the CPU that keeps every asymmetry of the published
model: 7 layers (full, window x 4, full, window), d 32, 8 query heads on
2 full / 4 window K/V heads, keys 24 wide and values 16, rotary on 8
lanes with two thetas, window 8 = the server's block, a sink on the
window layers only, layer 0 a dense SwiGLU, then 16 experts top-2 of
which 4 are held, a 97-row slice of a vocabulary.

Weights are the family's seeded ones at ``init_std`` 0.1 (N(0, 0.02) at
these widths leaves a layer's output far below the embedding, and every
context then gives the same token).  Nothing here is a measurement."""
import copy
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

from benchmark import drivers, run  # noqa: E402
from benchmark.families import post_ln, sparse_window  # noqa: E402
from benchmark.families import sparse_window_reference as ref  # noqa: E402
from benchmark.families.hybrid_ssm_reference import Leaf, layer_weights  # noqa: E402
from deeplearning4j_tpu.models.generation import TransformerGenerator  # noqa: E402
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers_hybrid import AttentionBlockRun  # noqa: E402
from deeplearning4j_tpu.parallel import GenerationServer  # noqa: E402
from deeplearning4j_tpu.parallel import generation_server as gs  # noqa: E402
from deeplearning4j_tpu.zoo import SparseWindowDecoder  # noqa: E402

TINY = dict(vocab_size=97, d_model=32, layer_pattern=[0, 1, 1, 1, 1, 0, 1],
            routed_layers=[0, 1, 1, 1, 1, 1, 1], n_heads=8, n_kv_heads=2,
            window_kv_heads=4, qk_dim=24, v_dim=16, rotary_dim=8,
            rope_theta=5e6, window_rope_theta=1e4, value_scale=0.707,
            window=8, window_sink=True, full_sink=False, d_ff=64,
            expert_ff=16, n_experts=16, top_k=2, held=[0, 4], eps=1e-5,
            seq_len=16)
SEED = 2 ** 31 + 23
F32 = {"family": "benchmark.families.sparse_window", "init_std": 0.1,
       "zoo_class": "deeplearning4j_tpu.zoo.sparse_window_decoder."
                    "SparseWindowDecoder",
       "ctor": dict(TINY, compute_dtype=None)}
BF16 = dict(F32, ctor=dict(TINY, compute_dtype="bfloat16", dtype="bfloat16"),
            precision={"master_weights": "bfloat16"})
SERVER = {"n_slots": 3, "max_len": 64, "block_size": 8, "tick_batch": 4,
          "prefix_cache": False}
paged_mod = importlib.import_module(
    "deeplearning4j_tpu.kernels.paged_attention")
expert_mod = importlib.import_module("deeplearning4j_tpu.kernels.expert_ffn")


@pytest.fixture(scope="module")
def seeded():
    """(net, the reference's tree, shape, offline generator) in float32."""
    shape = sparse_window.shape_of(F32)
    net = drivers.build_net(F32)
    drivers.seed_weights(net, sparse_window, shape, SEED)
    w = drivers.seed_tree(sparse_window, shape, sparse_window.seed_key(SEED))
    return net, w, shape, TransformerGenerator(net)


@pytest.fixture(scope="module")
def seeded_bf16():
    """(net, the reference's tree, shape): bfloat16 master weights."""
    shape = sparse_window.shape_of(BF16)
    net = drivers.build_net(BF16)
    init_dtypes = {str(a.dtype) for a in jax.tree_util.tree_leaves(net.params_tree)}
    drivers.seed_weights(net, sparse_window, shape, SEED, "bfloat16")
    w = drivers.seed_tree(sparse_window, shape, sparse_window.seed_key(SEED),
                          "bfloat16")
    return net, w, shape, init_dtypes


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
            for n in lengths]


def _close(program, reference):
    """Within 1e-4 of the largest logit's magnitude: float32 on both
    sides, the same equations in another order of operations (the
    program batches, caches, scans and sorts rows by expert; the
    reference does not)."""
    reference = np.asarray(reference)
    np.testing.assert_allclose(np.asarray(program), reference, rtol=0,
                               atol=1e-4 * np.abs(reference).max())


# ---------------------------------------------------------------------------
# the program against the reference, float32
# ---------------------------------------------------------------------------
def test_full_forward_equals_the_reference(seeded):
    """40 positions: five windows deep, every kind of layer."""
    net, w, shape, _ = seeded
    ids = _prompts([40])[0]
    _close(net.output(ids[None])[0], ref.lm_logits(w, shape, ids)[None])


def test_offline_prefill_then_decode_equals_the_reference(seeded):
    """Teacher-forced: the prompt's prefill (11 tokens: past one window),
    then one cached step per later token through the dense full cache
    and the window rings, give the reference's logits at every served
    position, four windows on; and ``generate()`` picks the reference's
    tokens."""
    net, w, shape, gen = seeded
    ids = _prompts([44])[0]
    t0 = 11
    emb_p, blk_ps, head_p = gen._params()
    runs_p = gen._stack_blocks(blk_ps)
    logits, ks, vs, rec = jax.jit(gen._prefill_rows)(
        emb_p, runs_p, head_p, ids[None, :t0])
    assert ks.shape == (2, 1, 2, t0, 24) and vs.shape == (2, 1, 2, t0, 16)
    assert rec["win_k"].shape == (5, 1, 4, 8, 24)
    assert rec["win_v"].shape == (5, 1, 4, 8, 16)
    pad = ((0, 0), (0, 0), (0, 0), (0, len(ids) - t0), (0, 0))
    kc, vc = jnp.pad(ks, pad), jnp.pad(vs, pad)
    got, step = [logits[0]], jax.jit(gen._step)
    for pos in range(t0, len(ids) - 1):
        logits, kc, vc, rec = step(emb_p, runs_p, head_p, kc, vc, rec,
                                   jnp.asarray(ids[pos:pos + 1]), pos)
        got.append(logits[0])
    _close(jnp.stack(got), ref.lm_logits(w, shape, ids)[None][t0 - 1:-1])
    out = gen.generate(ids[None, :t0], 30)[0]
    assert sparse_window.served_token_gaps(w, shape, out, t0).max() == 0.0


def _drive_by_hand(srv, prompt, slot, tokens):
    """The server's own admit and decode programs, dispatched as its
    scheduler dispatches them (which stays idle: nothing is submitted),
    teacher-forced with ``tokens``: the held logits after the admission
    and after every tick."""
    bs, t0 = srv.block_size, len(prompt)
    tb = -(-gs._bucket(t0, srv.max_len) // bs) * bs
    padded = np.zeros((1, tb), np.int32)
    padded[0, :t0] = prompt
    n_blocks = -(-(t0 + len(tokens) + 1) // bs)
    phys = 1 + slot * srv.max_blocks + np.arange(n_blocks, dtype=np.int32)
    table = np.zeros((srv.max_blocks,), np.int32)
    table[:n_blocks] = phys
    scatter = np.zeros((tb // bs,), np.int32)
    scatter[:min(len(scatter), n_blocks)] = phys[:len(scatter)]
    kc, vc, state = srv._admit_miss_fn(tb)(
        *srv._params, srv._kc, srv._vc, srv._state, jnp.asarray(padded),
        np.int32(t0), np.int32(slot), np.int32(len(tokens) + 1), np.int32(-1),
        jax.random.PRNGKey(0), np.float32(0), np.int32(srv._vocab),
        np.float32(1), jnp.asarray(scatter), jnp.asarray(table),
        jnp.zeros_like(table))
    held = [state["logits"][slot]]
    for tok in tokens:
        # the tick takes the argmax of the held logits: plant the token
        state = dict(state, logits=state["logits"].at[slot].set(
            jax.nn.one_hot(tok, srv._vocab)))
        kc, vc, state, *_ = srv._decode_scan(1, False)(*srv._params, kc, vc,
                                                       state)
        held.append(state["logits"][slot])
    srv._kc, srv._vc, srv._state = kc, vc, state
    return jnp.stack(held)


def test_server_prefill_then_decode_equals_the_reference(seeded):
    """The admit program at a padded bucket (11 tokens in 16) and the
    decode scan over the paged pool of the full layers and the slot's
    own window blocks: the reference's logits at every served position,
    over several blocks and several windows, in a slot that is not the
    first; the other slots' window blocks were never touched."""
    net, w, shape, _ = seeded
    ids = _prompts([44], seed=3)[0]
    t0 = 11
    with GenerationServer(net, **SERVER) as srv:
        assert srv._kc.shape == (2, 25, 2, 8, 24)     # full: 2 K/V heads
        assert srv._vc.shape == (2, 25, 2, 8, 16)
        assert srv._state["win_k"].shape == (5, 4, 4, 8, 24)   # 3 slots + 1
        got = _drive_by_hand(srv, ids[:t0], 1, ids[t0:-1])
        for key in ("win_k", "win_v"):
            ring = np.asarray(srv._state[key])
            # block 0 is the sink of the idle slots' masked writes
            assert not ring[:, [1, 3]].any() and ring[:, 2].all()
    _close(got, ref.lm_logits(w, shape, ids)[None][t0 - 1:-1])


@pytest.mark.parametrize("bucket", [16, 32])
def test_a_padded_bucket_arms_the_window_as_after_the_last_real_token(
        seeded, bucket):
    """An 11-token prompt prefilled alone and in a bucket: the same
    logits, the same full K/V rows, the same window rings -- row j the
    newest real position p with p % 8 == j: 8, 9, 10, 3 .. 7 -- and the
    same tally: a pad position takes no expert."""
    net, _, _, gen = seeded
    prompt = _prompts([11], seed=5)[0]
    emb_p, blk_ps, head_p = gen._params()
    runs_p = gen._stack_blocks(blk_ps)
    alone = jax.jit(gen._prefill_rows)(emb_p, runs_p, head_p, prompt[None])
    padded = np.full((1, bucket), 7, np.int32)      # pad with a live id
    padded[0, :11] = prompt
    logits, ks, vs, rec = jax.jit(gen._prefill_rows)(
        emb_p, runs_p, head_p, padded, jnp.int32(11))
    # float32 round-off of another trip count, not a cache one token on
    tol = dict(rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(logits, alone[0], **tol)
    np.testing.assert_allclose(ks[:, :, :, :11], alone[1], **tol)
    for key in ("win_k", "win_v"):
        np.testing.assert_allclose(rec[key], alone[3][key], **tol)
        assert np.asarray(rec[key]).all()           # a full ring
    np.testing.assert_array_equal(rec["routed"], alone[3]["routed"])
    assert int(rec["routed"][-1]) == 11 * 2 * 6     # tokens x top-k x layers
    # the ring, by position: the full layers' rows say which is which
    # (layer 5's rotated keys are not the window layers', so compare a
    # window layer's ring with its own whole-sequence rows)
    run = net.layers[2]                             # window x 4
    p = jax.tree_util.tree_map(lambda a: a[0], runs_p[1])
    x = jnp.zeros((1, 11, 32)) + jnp.arange(11)[None, :, None] / 11.0
    _, whole = AttentionBlockRun(**{**run.__dict__, "window": None}) \
        .sequence(p, x)
    _, ring = run.sequence(p, x)
    np.testing.assert_allclose(
        ring["k"][0], whole["k"][0][:, [8, 9, 10, 3, 4, 5, 6, 7]], **tol)


def test_requests_in_flight_together_each_equal_their_solo_run(seeded):
    """Three requests of different lengths on three slots, budgets 3, 19
    and 30 at scans of 4 ticks (one retires mid-scan), a fourth that
    takes over a freed slot and its window blocks: every one equals its
    solo run (alone in the server, afterwards) and the reference's
    greedy choice."""
    net, w, shape, _ = seeded
    prompts = _prompts([7, 13, 5, 20], seed=1)
    budgets = [3, 19, 30, 11]
    with GenerationServer(net, **SERVER) as srv:
        outs = [h.result(timeout=300) for h in
                [srv.submit_async(p, n_new=n) for p, n in zip(prompts, budgets)]]
        solo = [srv.submit_async(p, n_new=n).result(timeout=300)
                for p, n in zip(prompts, budgets)]
    for p, out, alone in zip(prompts, outs, solo):
        np.testing.assert_array_equal(out, alone)
        assert sparse_window.served_token_gaps(w, shape, out, len(p)).max() == 0.0


def test_the_kernel_route_serves_the_same_tokens(seeded, monkeypatch):
    """Both kernels in interpret mode inside the server's own programs
    (the pools aliased through the paged one, a window layer's ring read
    through its one-block table with its sink, the experts read out of
    the run's stacked matrices by a traced layer index): the tokens of
    the ``jax.numpy`` routes."""
    net, _, _, gen = seeded
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", "pallas")
    monkeypatch.setenv("DL4J_TPU_EXPERT_KERNEL", "pallas")
    prompts = _prompts([6, 11], seed=2)
    with GenerationServer(net, **dict(SERVER, tick_batch=2)) as srv:
        # the kernel route's pools: whole 128-lane rows, K and V apart
        assert srv._kc.shape[-1] == srv._vc.shape[-1] == 128
        assert srv._state["win_k"].shape == (5, 4, 4, 8, 128)
        outs = [h.result(timeout=600) for h in
                [srv.submit_async(p, n_new=12) for p in prompts]]
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, gen.generate(p[None], 12)[0])


def test_a_window_wider_than_a_block_waits_on_the_kernel_route(
        seeded, monkeypatch):
    """The reference routes page a ring over several blocks; the decode
    kernel patches a slot's last live block only, and says so."""
    net, _, _, gen = seeded
    prompt = _prompts([13], seed=6)[0]
    with GenerationServer(net, **dict(SERVER, block_size=4)) as srv:
        assert srv._state["win_k"].shape == (5, 7, 4, 4, 24)   # 2 blocks a slot
        out = srv.submit_async(prompt, n_new=20).result(timeout=300)
    np.testing.assert_array_equal(out, gen.generate(prompt[None], 20)[0])
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", "pallas")
    with pytest.raises(ValueError, match="a window fits one block"):
        GenerationServer(net, **dict(SERVER, block_size=4))


# ---------------------------------------------------------------------------
# the two kernels against their jax.numpy routes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", ["full_16_on_2", "window_ring_with_sink",
                                  "one_head_each_with_sink"])
def test_paged_kernel_with_unequal_widths_and_a_sink_equals_the_reference_route(
        case):
    """The decode scan's kernel in interpret mode against scatter + the
    gather reference, keys 24 wide in a 128-lane pool beside values 16
    wide: 1e-5 (an online softmax that starts from the sink against a
    whole one with the sink as a column); the written rows land in the
    right layer and nowhere else."""
    hq, hkv, mb, sink = {"full_16_on_2": (16, 2, 4, False),
                         "window_ring_with_sink": (8, 4, 1, True),
                         "one_head_each_with_sink": (4, 4, 4, True)}[case]
    L, B, dk, dv, bs = 2, 3, 24, 16, 8
    nb = 1 + B * mb
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    pad = lambda a: jnp.pad(a, [(0, 0)] * 4 + [(0, 128 - a.shape[-1])])
    kp = pad(jax.random.normal(ks[0], (L, nb, hkv, bs, dk)))
    vp = pad(jax.random.normal(ks[1], (L, nb, hkv, bs, dv)))
    q = jax.random.normal(ks[2], (B, hq, dk))
    kn = jax.random.normal(ks[3], (B, hkv, dk))
    vn = jax.random.normal(ks[4], (B, hkv, dv))
    b = 2.0 * jax.random.normal(ks[5], (hq,)) if sink else None
    table = jnp.asarray(1 + np.arange(B * mb).reshape(B, mb), jnp.int32)
    pos = jnp.asarray([3, 7, 5] if mb == 1 else [3, 17, 30], jnp.int32)
    wblk = jnp.take_along_axis(table, (pos // bs)[:, None], 1)[:, 0]
    wblk = wblk.at[2].set(0)                  # a slot retired mid-scan
    woff = pos % bs
    att, ko, vo = paged_mod._paged_decode_write_pallas(
        q, kn, vn, kp, vp, table, pos, wblk, woff, jnp.int32(1), dk ** -0.5, b)
    assert att.shape == (B, hq, dv)
    put = lambda pool, new: pool[1].at[wblk[:2], :, woff[:2], :].set(
        paged_mod.pad_head_dim(new[:2], 128))
    kl, vl = put(kp, kn), put(vp, vn)
    want = paged_mod.paged_decode_attention_reference(
        q, kl[..., :dk], vl[..., :dv], table, pos, dk ** -0.5, b)
    np.testing.assert_allclose(att, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(ko[1], kl)
    np.testing.assert_array_equal(vo[1], vl)
    np.testing.assert_array_equal(vo[0], vp[0])
    if sink:
        # the sink takes weight and gives no value: the rows shrink
        bare = paged_mod.paged_decode_attention_reference(
            q, kl[..., :dk], vl[..., :dv], table, pos, dk ** -0.5)
        assert not np.allclose(bare, want, atol=1e-3)


def test_the_read_only_kernels_say_what_they_do_not_walk(monkeypatch):
    monkeypatch.setattr(paged_mod, "_route", lambda: "pallas")
    q = jnp.zeros((2, 4, 24))
    kp, vp = jnp.zeros((5, 4, 8, 128)), jnp.zeros((5, 4, 8, 128))
    table, pos = jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="knows no sink"):
        paged_mod.paged_decode_attention(q, kp, vp, table, pos,
                                         sink=jnp.zeros((4,)))
    with pytest.raises(ValueError, match="pools of one width"):
        paged_mod.paged_verify_attention(q[:, None], kp, vp[..., :64], table,
                                         pos)


@pytest.mark.parametrize("case", ["a_layer_alone", "a_layer_of_a_run",
                                  "more_rows_than_a_tile"])
def test_expert_ffn_kernel_equals_its_jax_numpy_route(case, monkeypatch):
    """Interpret mode against the masked einsum, float32 sums in another
    order: 1e-5 of the largest output.  Expert 1 gets no row (no copy of
    its weights is started), expert 2 gets every row, some picks are of
    experts held elsewhere; with no pick held here at all the output is
    nought."""
    T, d, ff, held, k = 24, 32, 48, 4, 2
    if case == "more_rows_than_a_tile":
        monkeypatch.setattr(expert_mod, "_ROW_TILE", 8)    # 24 rows: 3 tiles
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (T, d))
    lead = (3,) if case == "a_layer_of_a_run" else ()
    wg, wu = (0.2 * jax.random.normal(key, lead + (held, d, ff))
              for key in ks[1:3])
    wd = 0.2 * jax.random.normal(ks[3], lead + (held, ff, d))
    layer = jnp.int32(2) if lead else None
    e = np.array(jax.random.randint(ks[4], (T, k), 0, held + 1))
    e[e == 1] = held
    e[:, 0] = 2
    e[:, 1][e[:, 1] == 2] = 0
    w = jax.random.uniform(ks[5], (T, k))
    want = expert_mod.expert_ffn_reference(x, jnp.asarray(e), w, wg, wu, wd,
                                           layer)
    monkeypatch.setenv("DL4J_TPU_EXPERT_KERNEL", "pallas")
    got = jax.jit(expert_mod.expert_ffn)(x, jnp.asarray(e), w, wg, wu, wd,
                                         layer)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * np.abs(want).max())
    dest, row_tok, first, tiles, live, n_live = expert_mod.expert_row_plan(
        jnp.asarray(e), held, expert_mod._ROW_TILE)
    assert int(n_live[0]) == 3 and 1 not in np.asarray(live)[:3]
    assert int(tiles[1]) == 0 and int(tiles[2]) == -(-T // expert_mod._ROW_TILE)
    none = expert_mod.expert_ffn(x, jnp.full((T, k), held), w, wg, wu, wd,
                                 layer)
    assert not np.asarray(none).any()


# ---------------------------------------------------------------------------
# the share and the model
# ---------------------------------------------------------------------------
def _one_routed_layer(w, shape):
    """(the first routed layer's reference weights, its program conf)."""
    p = layer_weights(w["win_routed"], 0)
    conf = lambda held: AttentionBlockRun(
        n_in=32, n_out=32, d_ff=16, n_experts=16, top_k=2, held=held,
        eps=1e-5)
    return p, conf


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference(seeded):
    """Four chips that hold experts 0-3, 4-7, 8-11, 12-15: each
    program's part of one routed layer's output (router over all 16,
    its own four experts computed), summed, is what the reference gives
    with all 16 held -- and each part alone is the reference's for that
    share.  What every chip computes alike (the router, the norm) is
    counted once: the parts are the experts' sums only."""
    _, w, shape, _ = seeded
    key = sparse_window.seed_key(SEED)
    whole_shape = dict(shape, held=(0, 16))
    p_all = layer_weights(
        sparse_window.weights_from_key(whole_shape, key)["win_routed"], 0)
    x = jax.random.normal(jax.random.PRNGKey(3), (20, 32))
    with jax.default_matmul_precision("highest"):
        n = ref.rms_norm(x, p_all["norm2"], 1e-5)
        uncut = ref.routed_ffn(p_all, n, lambda a: a, 2, 0)
    parts, tallies = [], []
    for first in (0, 4, 8, 12):
        _, conf = _one_routed_layer(w, shape)
        share = {k: (v[first:first + 4] if k in ("W_gate", "W_up", "W_down")
                     else v) for k, v in p_all.items()}
        y, tally = conf((first, 4))._ffn(share, x)
        parts.append(y - x)
        tallies.append(np.asarray(tally))
        with jax.default_matmul_precision("highest"):
            _close(y - x, ref.routed_ffn(share, n, lambda a: a, 2, first))
    _close(sum(parts), uncut)
    assert not np.allclose(parts[0], uncut, atol=1e-3)
    # every pair is held by exactly one share; each share saw them all
    assert sum(t[:-1].sum() for t in tallies) == 20 * 2
    assert {int(t[-1]) for t in tallies} == {20 * 2}


def test_selection_uses_score_plus_bias_and_weights_use_the_score():
    """Scores by hand: expert 5 has the lowest score and a bias that puts
    it first; it is selected, and its weight is its SCORE's share, not
    score + bias's."""
    d, E = 8, 6
    conf = AttentionBlockRun(n_in=d, n_out=d, d_ff=4, n_experts=E, top_k=2,
                             eps=1e-5)
    logits = np.array([2.0, 1.0, 0.0, -1.0, -2.0, -3.0], np.float32)
    x = np.zeros((1, d), np.float32)
    x[0, 0] = 1.0
    n = np.asarray(ref.rms_norm(jnp.asarray(x), jnp.ones(d), 1e-5))[0, 0]
    p = {"norm2": jnp.ones(d), "W_router": jnp.zeros((d, E)).at[0].set(logits / n),
         "e_bias": jnp.zeros(E).at[5].set(10.0),
         "W_gate": jnp.ones((E, d, 4)), "W_up": jnp.ones((E, d, 4)),
         # expert e answers with the constant e + 1 on lane 0
         "W_down": jnp.zeros((E, 4, d)).at[:, :, 0].set(
             (jnp.arange(E) + 1.0)[:, None])}
    y, tally = conf._ffn(p, jnp.asarray(x))
    r = 1.0 / (1.0 + np.exp(-logits))
    a = n / (1.0 + np.exp(-n)) * n * 4             # silu(g) * u, summed over ff
    want = (r[0] * 1.0 + r[5] * 6.0) / (r[0] + r[5]) * a
    np.testing.assert_allclose(float(y[0, 0] - x[0, 0]), want, rtol=1e-5)
    np.testing.assert_array_equal(tally, [1, 0, 0, 0, 0, 1, 2])


def test_held_and_absent_rows_add_up_to_tokens_times_top_k(seeded):
    """Two requests through the scheduler: every token of every prompt
    and every decode tick makes top-k pairs in each of the 6 routed
    layers, held here or elsewhere; the held experts' turns are the
    routed layers run x 4; one load sample a decode dispatch."""
    net = seeded[0]
    before = drivers.registry_snapshot()
    prompts, n_new = _prompts([9, 14], seed=7), 10
    with GenerationServer(net, **SERVER) as srv:
        for h in [srv.submit_async(p, n_new=n_new) for p in prompts]:
            h.result(timeout=300)
    after = drivers.registry_snapshot()
    delta = lambda name: after["counters"][name] - before["counters"].get(name, 0)
    rows = 'generation_server_expert_rows_total{kind="%s"}'
    tokens = sum(len(p) for p in prompts) + 2 * n_new
    assert delta(rows % "held") + delta(rows % "absent") == tokens * 2 * 6
    assert 0 < delta(rows % "held") < delta(rows % "absent")
    ticks = delta("generation_server_ticks_total")
    assert delta("generation_server_expert_calls_total") == (ticks + 2) * 6 * 4
    load = lambda s: s["histograms"].get(
        "generation_server_expert_load_ratio", {"count": 0, "sum": 0.0})
    scans = sum(v - before["counters"].get(k, 0)
                for k, v in after["counters"].items()
                if k.startswith("generation_server_scan_ticks_total"))
    assert load(after)["count"] - load(before)["count"] == scans
    assert load(after)["sum"] - load(before)["sum"] >= scans     # max >= mean
    # both kinds' tables are walked: a window's one block is always live
    live = 'generation_server_paged_blocks_total{kind="live"}'
    assert delta(live) >= 2 * delta("generation_server_tokens_emitted_total") - 2


# ---------------------------------------------------------------------------
# what the stack refuses, each by its message
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kw, what", [
    ({"prefix_cache": True}, "prefix_cache=True"),
    ({"speculative": {"k": 2}}, "speculative decode"),
    ({"host_tier_blocks": 4}, "host_tier_blocks > 0"),
    ({"tp": 2}, "tp > 1"),
    ({"devices": 2}, "tp > 1")],
    ids=["prefix_cache", "speculative", "host_tier", "tp", "two_devices"])
def test_the_stack_refuses_at_construction(seeded, kw, what):
    net = seeded[0]
    if "devices" in kw:
        kw = {"devices": jax.devices()[:2]}
    with pytest.raises(ValueError, match=f"{what} is not supported for a net "
                                         "with AttentionBlockRun layers"):
        GenerationServer(net, **dict(SERVER, **kw))


@pytest.mark.parametrize("call", ["export_prefix", "import_blocks",
                                  "prefill_async"])
def test_the_stack_refuses_the_hand_off_calls(seeded, call):
    with GenerationServer(seeded[0], **SERVER) as srv:
        with pytest.raises(ValueError, match=f"{call} is not supported for a "
                                             "net with AttentionBlockRun.*not "
                                             "restorable from a shared prefix"):
            getattr(srv, call)(np.arange(9, dtype=np.int32))
        assert srv.stats()["live_slots"] == 0


@pytest.mark.parametrize("change, message", [
    ({"n_kv_heads": 1}, "the full-attention runs of one stack share one K/V "
                        "pool"),
    ({"window": 4}, "the window runs of one stack share one K/V pool"),
    ({"held": (0, 2)}, "hold as many experts each")],
    ids=["two_full_kinds", "two_window_kinds", "two_shares"])
def test_the_generator_refuses_two_shapes_of_a_kind(change, message):
    """One pool a kind: a second full kind, a second window, a second
    share in one stack are refused by name."""
    conf = SparseWindowDecoder(**dict(TINY, compute_dtype=None)).conf()
    odd = conf.layers[3 if "n_kv_heads" in change else 4]
    for field, value in change.items():
        setattr(odd, field, value)
    with pytest.raises(ValueError, match=message):
        TransformerGenerator(MultiLayerNetwork(conf).init())


# ---------------------------------------------------------------------------
# the family seam
# ---------------------------------------------------------------------------
def test_the_family_gives_what_the_harness_asks_for():
    missing = [a for a in post_ln.REQUIRED if not hasattr(sparse_window, a)]
    assert not missing, missing
    assert not hasattr(sparse_window, "follow_training")
    assert set(sparse_window.KERNEL_COSTS) == {"paged_attention", "expert_ffn"}
    assert drivers.family_of(F32) is sparse_window
    shape = sparse_window.shape_of(F32)
    assert (shape["layers"], shape["full_layers"], shape["win_layers"],
            shape["routed_layers"], shape["vocab"], shape["held"]) == (
                7, 2, 5, 6, 97, (0, 4))
    assert [g for g, _ in ref.layer_kinds(shape)] == [
        "full_dense", "win_routed", "win_routed", "win_routed", "win_routed",
        "full_routed", "win_routed"]


def test_the_published_shape_and_its_costs_by_hand():
    """The configuration file's sizes: the cut's parameter count as ISSUE
    33 reckons it (3.43B), nothing of a width changed from the
    catalog's row, and the two kernels' costs by hand."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2-flash.json")) as f:
        config = json.load(f)
    pub = config["published"]
    changed = {k for k, v in pub.items() if config[k] != v}
    assert changed == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    c = config["ctor"]
    assert (c["d_model"], c["n_heads"], c["n_kv_heads"], c["window_kv_heads"],
            c["qk_dim"], c["v_dim"], c["d_ff"], c["expert_ff"], c["n_experts"],
            c["top_k"], c["window"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["swa_num_key_value_heads"],
        pub["head_dim"], pub["v_head_dim"], pub["intermediate_size"],
        pub["moe_intermediate_size"], pub["n_routed_experts"],
        pub["num_experts_per_tok"], pub["sliding_window"])
    assert c["rotary_dim"] == 64 == round(pub["partial_rotary_factor"] * 192)
    assert c["layer_pattern"] == pub["hybrid_layer_pattern"][:7]
    assert c["routed_layers"] == pub["moe_layer_freq"][:7]
    shape = sparse_window.shape_of(config)
    assert (shape["layers"], shape["full_layers"], shape["win_layers"],
            shape["routed_layers"], shape["vocab"], shape["held"]) == (
                7, 2, 5, 6, 19072, (0, 16))
    counts = {"full_dense": 1, "full_routed": 1, "win_routed": 5}
    leaves = sum(int(np.prod(inner)) * counts[g]
                 for g, spec in ref.leaf_specs(shape).items()
                 for inner, _ in spec.values())
    full, win = 89_128_960, 94_371_840          # a layer's attention
    assert leaves == (full + 3 * 4096 * 16384 + 2 * 4096
                      + 5 * (win + 64) + full
                      + 6 * (4096 * 256 + 256 + 16 * 3 * 4096 * 2048 + 2 * 4096))
    assert round((leaves + 2 * 19072 * 4096 + 4096) / 1e9, 2) == 3.43
    assert sparse_window.pairs_per_token(shape) == 0.5
    # one request: 100-token prompt, tokens 1..3 in the window
    work = sparse_window.serve_work(shape, [(100, 1, 3)])
    assert (work["decode_tokens"], work["ctx_sum"], work["win_ctx_sum"],
            work["prefills"], work["prefill_tokens"]) == (
                2, 101 + 102, 101 + 102, 1, 100)
    assert 6 * 16 * 0.95 < work["prefill_expert_reads"] < 6 * 16
    # a token at context 300: a window layer reads 128 keys of it
    work = sparse_window.serve_work(shape, [(299, 2, 2)])
    assert (work["ctx_sum"], work["win_ctx_sum"]) == (300, 128)
    facts = {"ctx_sum": 1000.0, "win_ctx_sum": 128.0}
    paged = sparse_window.KERNEL_COSTS["paged_attention"](shape, facts, {}, 0, {})
    assert paged == {
        "bytes": 2.0 * 320 * (2 * 4 * 1000 + 5 * 8 * 128),
        "flops": 2.0 * 320 * 64 * (2 * 1000 + 5 * 128)}
    # 12 events: two decode ticks of the 6 routed layers, no prefill
    facts = {"decode_tokens": 512.0, "prefill_tokens": 0.0, "prefills": 0.0,
             "prefill_expert_reads": 0.0}
    ffn = sparse_window.KERNEL_COSTS["expert_ffn"](shape, facts, {}, 12, {})
    pairs = 512 * 6 * 0.5
    assert ffn == {"flops": 6.0 * 4096 * 2048 * pairs,
                   "bytes": 2.0 * (12 * 16 * 3 * 4096 * 2048
                                   + pairs * 2 * 4096)}


def test_the_lazy_tree_goes_through_seed_tree_and_seed_weights(seeded_bf16):
    """bfloat16 master weights: ``seed_tree`` only records the rounding
    (no leaf is drawn), the program's leaves are bfloat16 in the
    program's layout -- run 2 of the net is window layers 0..3 of the
    reference -- and the reference's layer weights are the same rounded
    values in float32."""
    net, w, shape, init_dtypes = seeded_bf16
    leaves = jax.tree_util.tree_leaves(w)
    assert leaves and all(isinstance(a, Leaf) for a in leaves)
    assert {tuple(str(c) for c in a.casts) for a in leaves} == {
        ("bfloat16", "float32")}
    assert init_dtypes == {"bfloat16"}    # init() too: the ctor's ``dtype``
    tree = net.params_tree
    assert {str(a.dtype) for a in jax.tree_util.tree_leaves(tree)} == {"bfloat16"}
    layout = sparse_window.layout_of(net)
    assert layout == (("emb",), ("full_dense", 0, 1), ("win_routed", 0, 4),
                      ("full_routed", 0, 1), ("win_routed", 4, 1), ("head",))
    layer2 = layer_weights(w["win_routed"], 2)
    assert set(layer2) == set(tree["layer_2"]) and "sink" in layer2
    for name, a in layer2.items():
        assert a.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(tree["layer_2"][name][2].astype(jnp.float32)),
            np.asarray(a))
    assert tree["layer_2"]["W_gate"].shape == (4, 4, 32, 16)
    assert "sink" not in tree["layer_1"] and "W_router" not in tree["layer_1"]
    assert tree["layer_5"]["W"].shape == (97, 32)            # its own table
    back = sparse_window.from_program(tree, layout)
    assert back["win_routed"]["Wk"].shape == (5, 32, 4 * 24)
    norms = sparse_window.leaf_norms(back)
    assert norms["win_routed.sink"].shape == (5,)
    assert len(norms) == 1 + 9 + 11 + 12 + 2


def test_one_copy_of_the_weights_and_the_pools_gauges(seeded):
    """The server's snapshot of a stack of runs IS the net's tree; the
    window rings' bytes and both kinds' pools' are on the gauges."""
    net = seeded[0]
    with GenerationServer(net, **SERVER) as srv:
        emb_p, runs_p, head_p = srv._params
        for i, p in enumerate(runs_p, start=1):
            for name, a in p.items():
                assert a is net.params_tree[f"layer_{i}"][name], (i, name)
        assert head_p["W"] is net.params_tree["layer_5"]["W"]
        gauges = drivers.registry_snapshot()["gauges"]
        ring = 5 * 4 * 4 * 8 * (24 + 16) * 4
        assert gauges["generation_server_window_cache_bytes"] == ring
        assert gauges['generation_server_kv_pool_bytes{kind="window"}'] == ring
        assert gauges['generation_server_kv_pool_bytes{kind="full"}'] == (
            2 * 25 * 2 * 8 * (24 + 16) * 4)
        assert gauges["generation_server_recurrent_state_bytes"] == 0


# ---------------------------------------------------------------------------
# a cell of it through run.run_cell, and the faults that must fail it
# ---------------------------------------------------------------------------
CELL = {"driver": "serve_closed",
        "server": {"compute_dtype": "bfloat16", "n_slots": 4, "max_len": 64,
                   "block_size": 8, "tick_batch": 2, "prefix_cache": False},
        "traffic": {"loop": "closed", "clients": 4,
                    "prompt_len": {"dist": "lognormal", "median": 12,
                                   "sigma": 0.5, "lo": 9, "cap": 16},
                    "n_new": {"dist": "uniform", "lo": 16, "hi": 40},
                    "shared_prefix": 0, "sizes_seed": 0, "n_sizes": 64,
                    "poll_ms": 4, "ramp_seconds": 0.3, "trace_seconds": 0.2,
                    "compare_requests": 3},
        "limits": {"token_gap": 0.12}}


def _stale_window(monkeypatch):
    """Admissions that do not arm the slot's window blocks: the slot
    decodes on from the rings its last occupant left (or from nothing)."""
    whole = GenerationServer._arm_slot
    monkeypatch.setattr(
        GenerationServer, "_arm_slot", lambda self, *a, **kw: whole(self, *a[:12]))


@pytest.mark.parametrize("fault", [None, _stale_window],
                         ids=["sound", "stale_window"])
def test_a_cell_of_the_family_runs_and_its_fault_fails(monkeypatch, fault):
    """Sound: token_gap under 0.12 (bfloat16 against float32, the limit
    of the accepted serve cells' rehearsals); window rings left stale at
    admission read well above it."""
    if fault is not None:
        fault(monkeypatch)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    result = run.run_cell("a-cell-of-the-sparse-window-family", manifest, CELL,
                          copy.deepcopy(BF16), SEED, 0.5, 0, jax.devices()[:1], {})
    assert result["correct"] is (fault is None), result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["compared"]) == {"token_gap"}


def test_the_control_fails_where_the_program_passes(seeded_bf16):
    """bfloat16 program, offline: its tokens lie within 0.12 of the
    reference's best on every prompt; the control's (float8 operands,
    the router's with them: one precision below) do not."""
    net, w, shape, _ = seeded_bf16
    gen = TransformerGenerator(net, compute_dtype="bfloat16")
    worst = {None: 0.0, sparse_window.CONTROL: 0.0}
    for prompt in _prompts([13, 13, 13], seed=4):
        out = gen.generate(prompt[None], 24)[0]
        for quant in worst:
            gaps = sparse_window.served_token_gaps(w, shape, out, len(prompt),
                                                   quant)
            worst[quant] = max(worst[quant], float(gaps.max()))
    assert worst[None] < 0.12 < worst[sparse_window.CONTROL], worst
    with pytest.raises(ValueError, match="unknown control precision"):
        sparse_window.served_token_gaps(w, shape, out, len(prompt), "fp4")


def test_a_train_cell_of_the_family_fails_plainly():
    with pytest.raises(SystemExit, match="no training reference"):
        run.run_cell("a-train-cell", {"per_layer": []}, {"driver": "train"},
                     F32, SEED, 0.5, 0, [], {})


def test_the_new_cell_s_files_are_what_the_manifest_says():
    """The cell as ISSUE 33 gives it, its configuration, and the three
    metric files, each read by a reduction the harness has."""
    from benchmark import readers
    name = "mimo-v2-flash.closed-long-reasoning"
    manifest, cell, config = run.cell_files(name)
    assert cell["driver"] == "serve_closed" and config["family"].endswith(
        "sparse_window")
    assert cell["server"] == {"compute_dtype": "bfloat16", "n_slots": 256,
                              "max_len": 2048, "block_size": 128,
                              "tick_batch": 8, "prefix_cache": False}
    t = cell["traffic"]
    assert (t["clients"], t["prompt_len"], t["n_new"], t["poll_ms"],
            t["trace_seconds"], t["compare_requests"]) == (
        256, {"dist": "lognormal", "median": 192, "sigma": 0.6, "lo": 32,
              "cap": 512}, {"dist": "uniform", "lo": 512, "hi": 1536}, 4, 5, 4)
    assert 10 <= t["ramp_seconds"] <= 20
    assert t["prompt_len"]["cap"] + t["n_new"]["hi"] <= cell["server"]["max_len"]
    ours = [m for m in manifest["per_layer"] if name in m.get("workloads", [])]
    for m in ours:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            assert json.load(f)["reader"]["kind"] in readers.READERS
    assert {m["name"] for m in ours if m["workloads"] == [name]} == {
        "expert_ffn_roofline", "expert_rows_mean", "expert_load_ratio"}
    reported = {m["name"] for m in manifest["end_to_end"]
                if name in m.get("workloads", [name])}
    assert reported == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
