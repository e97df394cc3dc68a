"""The gated sparse-expert family (``benchmark/families/
gated_sparse_window.py``) and the program it drives, at a tiny size on
the CPU that keeps every asymmetry of the published model: 5 layers
(full, sliding x 3, full), d 32, 6 query heads in a full layer and 8 in
a sliding one on 2 K/V heads of 16, a YaRN base on 8 lanes of the full
layers and a plain one on all 16 of the sliding ones, a sigmoid gate a
query head, window 32 over FOUR of the server's 8-position blocks,
layer 0 a dense SwiGLU, then 16 experts top-4 ALL held, a shared expert
beside them, the routed sum times 2.5, a 97-row vocabulary.

Weights are the family's seeded ones at ``init_std`` 0.1 (N(0, 0.02) at
these widths leaves a layer's output far below the embedding, and every
context then gives the same token).  Nothing here is a measurement."""
import copy
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
from benchmark.families import gated_sparse_window as family  # noqa: E402
from benchmark.families import gated_sparse_window_reference as ref  # noqa: E402
from benchmark.families import post_ln  # noqa: E402
from benchmark.families.hybrid_ssm_reference import layer_weights  # noqa: E402
from deeplearning4j_tpu.models.generation import TransformerGenerator  # noqa: E402
from deeplearning4j_tpu.nn.conf import layers_hybrid  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers_hybrid import AttentionBlockRun  # noqa: E402
from deeplearning4j_tpu.parallel import GenerationServer  # noqa: E402
from deeplearning4j_tpu.parallel import generation_server as gs  # noqa: E402

YARN = {"rope_type": "yarn", "factor": 8.0,
        "original_max_position_embeddings": 16, "beta_fast": 4.0,
        "beta_slow": 1.0, "attention_factor": 1.2}
TINY = dict(vocab_size=97, d_model=32, layer_pattern=[0, 1, 1, 1, 0],
            routed_layers=[0, 1, 1, 1, 1], n_heads=6, window_heads=8,
            n_kv_heads=2, window_kv_heads=2, qk_dim=16, v_dim=16,
            rotary_dim=8, window_rotary_dim=16, rope_theta=5e5,
            window_rope_theta=1e4, rope_scaling=YARN,
            window_rope_scaling=None, value_scale=None, window=32,
            window_sink=False, full_sink=False, gate=True, d_ff=64,
            expert_ff=16, shared_ff=16, routed_scale=2.5, n_experts=16,
            top_k=4, held=None, eps=1e-6, seq_len=128)
SEED = 2 ** 31 + 23
F32 = {"family": "benchmark.families.gated_sparse_window", "init_std": 0.1,
       "zoo_class": "deeplearning4j_tpu.zoo.sparse_window_decoder."
                    "SparseWindowDecoder",
       "ctor": dict(TINY, compute_dtype=None)}
BF16 = dict(F32, ctor=dict(TINY, compute_dtype="bfloat16", dtype="bfloat16"),
            precision={"master_weights": "bfloat16"})
SERVER = {"n_slots": 3, "max_len": 128, "block_size": 8, "tick_batch": 4,
          "prefix_cache": False}
CELL = "laguna-xs.2.closed-code-context"


@pytest.fixture(scope="module")
def seeded():
    """(net, the reference's tree, shape, offline generator) in float32."""
    shape = family.shape_of(F32)
    net = drivers.build_net(F32)
    drivers.seed_weights(net, family, shape, SEED)
    w = drivers.seed_tree(family, shape, family.seed_key(SEED))
    return net, w, shape, TransformerGenerator(net)


@pytest.fixture(scope="module")
def seeded_bf16():
    shape = family.shape_of(BF16)
    net = drivers.build_net(BF16)
    drivers.seed_weights(net, family, shape, SEED, "bfloat16")
    w = drivers.seed_tree(family, shape, family.seed_key(SEED), "bfloat16")
    return net, w, shape


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
            for n in lengths]


def _close(program, reference):
    """Within 1e-4 of the largest logit's magnitude: float32 on both
    sides, the same equations in another order of operations."""
    reference = np.asarray(reference)
    np.testing.assert_allclose(np.asarray(program), reference, rtol=0,
                               atol=1e-4 * np.abs(reference).max())


# ---------------------------------------------------------------------------
# the program against the reference, float32, logits
# ---------------------------------------------------------------------------
def test_full_forward_equals_the_reference(seeded):
    """100 positions: three windows deep, every kind of layer."""
    net, w, shape, _ = seeded
    ids = _prompts([100])[0]
    _close(net.output(ids[None])[0], ref.lm_logits(w, shape, ids)[None])


def test_offline_prefill_then_decode_equals_the_reference(seeded):
    """Teacher-forced: the prompt's prefill (41 tokens: past one window,
    five blocks into the four-block ring), then one cached step per
    later token through the dense full cache and the rings, give the
    reference's logits at every served position until the ring has
    wrapped three times; and ``generate()`` picks the reference's
    tokens."""
    net, w, shape, gen = seeded
    ids = _prompts([110])[0]
    t0 = 41
    emb_p, blk_ps, head_p = gen._params()
    runs_p = gen._stack_blocks(blk_ps)
    logits, ks, vs, rec = jax.jit(gen._prefill_rows)(
        emb_p, runs_p, head_p, ids[None, :t0])
    assert ks.shape == vs.shape == (2, 1, 2, t0, 16)
    assert rec["win_k"].shape == rec["win_v"].shape == (3, 1, 2, 32, 16)
    assert rec["routed"].shape == (16 + 1,) and rec["reached"].shape == (1,)
    pad = ((0, 0), (0, 0), (0, 0), (0, len(ids) - t0), (0, 0))
    kc, vc = jnp.pad(ks, pad), jnp.pad(vs, pad)
    got, step = [logits[0]], jax.jit(gen._step)
    for pos in range(t0, len(ids) - 1):
        logits, kc, vc, rec = step(emb_p, runs_p, head_p, kc, vc, rec,
                                   jnp.asarray(ids[pos:pos + 1]), pos)
        got.append(logits[0])
    _close(jnp.stack(got), ref.lm_logits(w, shape, ids)[None][t0 - 1:-1])
    out = gen.generate(ids[None, :t0], 60)[0]
    assert family.served_token_gaps(w, shape, out, t0).max() == 0.0


def _drive_by_hand(srv, prompt, slot, tokens, bucket):
    """Admit ``prompt`` into ``slot`` through the admit program at
    ``bucket``, then one decode scan of one tick per token of
    ``tokens``, each planted as the slot's argmax.  Returns the held
    logits [1 + len(tokens), vocab]."""
    t0, bs, mb = len(prompt), srv.block_size, srv.max_blocks
    padded = np.zeros((1, bucket), np.int32)    # 2-D: the operands one by one
    padded[0, :t0] = prompt
    n_blocks = -(-(t0 + len(tokens) + 1) // bs)
    phys = np.arange(1, n_blocks + 1, dtype=np.int32) + slot * mb
    table = np.zeros((mb,), np.int32)
    table[:n_blocks] = phys
    scatter = np.zeros((bucket // bs,), np.int32)
    scatter[:min(len(scatter), n_blocks)] = phys[:len(scatter)]
    kc, vc, state = srv._admit_miss_fn(bucket)(
        *srv._params, srv._kc, srv._vc, srv._state, jnp.asarray(padded),
        np.int32(t0), np.int32(slot), np.int32(len(tokens) + 1), np.int32(-1),
        jax.random.PRNGKey(0), np.float32(0), np.int32(srv._vocab),
        np.float32(1), jnp.asarray(scatter), jnp.asarray(table),
        jnp.zeros_like(table))
    held = [state["logits"][slot]]
    for tok in tokens:
        state = dict(state, logits=state["logits"].at[slot].set(
            jax.nn.one_hot(tok, srv._vocab)))
        kc, vc, state, *_ = srv._decode_scan(1, False)(*srv._params, kc, vc,
                                                       state)
        held.append(state["logits"][slot])
    srv._kc, srv._vc, srv._state = kc, vc, state
    return jnp.stack(held)


@pytest.mark.parametrize("route", ["reference", "pallas"])
def test_server_prefill_then_decode_equals_the_reference(seeded, monkeypatch,
                                                         route):
    """The admit program at a padded bucket (41 tokens in 64) arms all
    four blocks of the slot's ring as after the last REAL token, and the
    decode scan writes on at ``pos % 32 // 8`` of it while it reads all
    four: the reference's logits at every served position, the ring
    wrapped twice more, in a slot that is not the first; the other
    slots' ring blocks were never touched.  On the reference routes and
    on the kernel's (interpret mode: the pools aliased through it, the
    write block told apart from the last block read)."""
    net, w, shape, _ = seeded
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", route)
    monkeypatch.setenv("DL4J_TPU_EXPERT_KERNEL", route)
    ids = _prompts([110], seed=3)[0]
    t0 = 41
    with GenerationServer(net, **SERVER) as srv:
        lanes = 128 if route == "pallas" else 16
        heads = 1 if route == "pallas" else 2       # two 16-wide heads a row
        assert srv._kc.shape == (2, 49, heads, 8, lanes)
        assert srv._state["win_k"].shape == (3, 3 * 4 + 1, heads, 8, lanes)
        got = _drive_by_hand(srv, ids[:t0], 1, ids[t0:-1], 64)
        for key in ("win_k", "win_v"):
            ring = np.asarray(srv._state[key])
            # block 0 is the sink of the idle slots' masked writes
            assert not ring[:, 1:5].any() and not ring[:, 9:].any()
            assert ring[:, 5:9, :, :, :16].all()
    _close(got, ref.lm_logits(w, shape, ids)[None][t0 - 1:-1])


@pytest.mark.parametrize("bucket", [48, 64])
def test_a_padded_bucket_arms_the_ring_as_after_the_last_real_token(
        seeded, bucket):
    """A 41-token prompt prefilled alone and in a bucket: the same
    logits, full K/V rows, rings -- row j the newest real position p
    with p % 32 == j: 32 .. 40, then 9 .. 31 -- and the same tally: a
    pad position takes no expert and reaches none."""
    net, _, _, gen = seeded
    prompt = _prompts([41], seed=5)[0]
    emb_p, blk_ps, head_p = gen._params()
    runs_p = gen._stack_blocks(blk_ps)
    alone = jax.jit(gen._prefill_rows)(emb_p, runs_p, head_p, prompt[None])
    padded = np.full((1, bucket), 7, np.int32)      # pad with a live id
    padded[0, :41] = prompt
    logits, ks, vs, rec = jax.jit(gen._prefill_rows)(
        emb_p, runs_p, head_p, padded, jnp.int32(41))
    tol = dict(rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(logits, alone[0], **tol)
    np.testing.assert_allclose(ks[:, :, :, :41], alone[1], **tol)
    for key in ("win_k", "win_v"):
        np.testing.assert_allclose(rec[key], alone[3][key], **tol)
    for key in ("routed", "reached"):
        np.testing.assert_array_equal(rec[key], alone[3][key])
    per_expert, pairs = np.split(np.asarray(rec["routed"]), [16])
    assert pairs == 41 * 4 * 4                  # tokens x top-k x layers
    assert per_expert.sum() == pairs            # every expert is held
    assert 4 <= int(rec["reached"][0]) <= 4 * 16    # a layer a call, <= held
    run = net.layers[2]                         # sliding x 3
    p = jax.tree_util.tree_map(lambda a: a[0], runs_p[1])
    x = jnp.zeros((1, 41, 32)) + jnp.arange(41)[None, :, None] / 41.0
    _, whole = AttentionBlockRun(**{**run.__dict__, "window": None}) \
        .sequence(p, x)
    _, ring = run.sequence(p, x)
    order = list(range(32, 41)) + list(range(9, 32))
    np.testing.assert_allclose(ring["k"][0], whole["k"][0][:, order], **tol)


def test_slots_retired_and_re_armed_mid_ring_each_equal_their_solo_run(
        seeded):
    """Five requests on three slots at scans of 4 ticks: prompts that
    wrap the four-block ring more than once (70, 45) beside short ones,
    budgets that retire a slot mid-scan and mid-ring, two later requests
    that take over freed slots whose rings are part-written: every one
    equals its solo run (alone in the server, afterwards) and the
    reference's greedy choice."""
    net, w, shape, _ = seeded
    prompts = _prompts([70, 13, 45, 37, 9], seed=1)
    budgets = [21, 3, 40, 30, 50]
    with GenerationServer(net, **SERVER) as srv:
        outs = [h.result(timeout=600) for h in
                [srv.submit_async(p, n_new=n) for p, n in zip(prompts, budgets)]]
        solo = [srv.submit_async(p, n_new=n).result(timeout=600)
                for p, n in zip(prompts, budgets)]
    for p, out, alone in zip(prompts, outs, solo):
        np.testing.assert_array_equal(out, alone)
        assert family.served_token_gaps(w, shape, out, len(p)).max() == 0.0


def test_the_kernel_route_serves_the_same_tokens(seeded, monkeypatch):
    """Both kernels in interpret mode inside the server's own programs,
    requests in flight together, a slot re-armed: the tokens of offline
    decode."""
    net, _, _, gen = seeded
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", "pallas")
    monkeypatch.setenv("DL4J_TPU_EXPERT_KERNEL", "pallas")
    prompts = _prompts([45, 6, 11, 38], seed=2)
    with GenerationServer(net, **dict(SERVER, tick_batch=2)) as srv:
        assert srv._state["win_k"].shape == (3, 13, 1, 8, 128)
        outs = [h.result(timeout=600) for h in
                [srv.submit_async(p, n_new=30) for p in prompts]]
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, gen.generate(p[None], 30)[0])


# ---------------------------------------------------------------------------
# each new mechanism matters; YaRN; the shares
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("leaf", ["Wg", "Ws_down"],
                         ids=["the_gate", "the_shared_expert"])
def test_zeroing_it_fails_the_comparison(seeded, leaf):
    """With the gate's matrix zeroed (every head times 1/2) or the shared
    expert's output zeroed, in the program alone, the logits leave the
    reference's by far more than ``_close`` allows."""
    net, w, shape, _ = seeded
    ids = _prompts([60], seed=4)[0]
    want = np.asarray(ref.lm_logits(w, shape, ids)[None])
    tree = net.params_tree
    try:
        net.params_tree = {
            k: ({**v, leaf: jnp.zeros_like(v[leaf])} if leaf in v else v)
            for k, v in tree.items()}
        got = np.asarray(net.output(ids[None])[0])
    finally:
        net.params_tree = tree
    assert np.abs(got - want).max() > 100 * 1e-4 * np.abs(want).max()
    with pytest.raises(AssertionError):
        _close(got, want)


def test_yarn_inv_freq_is_the_formula_at_the_published_numbers():
    """The program's ``yarn_inv_freq`` and the reference's against a
    literal transcription of ``transformers``' ``_compute_yarn_
    parameters`` at Laguna-XS.2's numbers (64 rotary lanes, theta
    500,000, factor 64, original 4,096, beta_fast 64, beta_slow 1): the
    first pairs are left alone, the last are divided by 64, a ramp
    between; cos and sin carry the stated attention factor."""
    import math
    D, base, factor, L0, fast, slow = 64, 500000.0, 64.0, 4096.0, 64.0, 1.0
    dim = lambda r: D * math.log(L0 / (r * 2 * math.pi)) / (2 * math.log(base))
    low, high = max(math.floor(dim(fast)), 0), min(math.ceil(dim(slow)), D - 1)
    assert (low, high) == (5, 16)
    want = []
    for i in range(D // 2):
        extra = base ** (-2.0 * i / D)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extra / factor * ramp + extra * (1.0 - ramp))
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-xs.2.json")) as f:
        scaling = json.load(f)["ctor"]["rope_scaling"]
    got, att = layers_hybrid.yarn_inv_freq(D, base, scaling)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert att == 1.4158883083359672
    np.testing.assert_allclose(
        ref.yarn_inv_freq(D, base, factor, L0, fast, slow), want, rtol=2e-6)
    np.testing.assert_allclose(got[:6], [base ** (-2.0 * i / D)
                                         for i in range(6)], rtol=1e-12)
    np.testing.assert_allclose(got[16:] * 64, [base ** (-2.0 * i / D)
                                               for i in range(16, 32)],
                               rtol=1e-12)
    # the rotated lanes carry the factor; the rest pass through
    x = jnp.ones((3, 1, 128))
    y = layers_hybrid.rotate_half(x, jnp.arange(3), 64, base, scaling)
    np.testing.assert_allclose(y[0, 0, :64], att, rtol=1e-6)
    np.testing.assert_array_equal(y[:, :, 64:], x[:, :, 64:])


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference(seeded):
    """Guide model-configs section 4's test, with a shared expert: a
    routed layer's feed-forward computed as four chips would, each
    holding 4 of the 16 experts (its own slice of the stacked matrices,
    its ``held`` = (4 i, 4)) and only the first of them the shared
    expert, adds up to the uncut reference's layer: the routed parts of
    all shares plus the shared expert counted ONCE."""
    net, w, shape, _ = seeded
    run = net.layers[2]                          # sliding, routed x 3
    p = layer_weights({k: v for k, v in w["win_routed"].items()
                       if not isinstance(v, ref.ExpertLeaf)}, 1)
    experts = {k: w["win_routed"][k].layer(1) for k in ref.EXPERT_LEAVES}
    x = jax.random.normal(jax.random.PRNGKey(3), (23, 32))
    n = ref.rms_norm(x, p["norm2"], shape["eps"])
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_ffn(p, n, lambda a: a, shape["top_k"], (0, 16),
                               shape["routed_scale"],
                               lambda e: [experts[k][e]
                                          for k in ref.EXPERT_LEAVES])
    parts, tallies = [], []
    for i in range(4):
        share = AttentionBlockRun(**{
            **run.__dict__, "held": (4 * i, 4),
            "shared_ff": run.shared_ff if i == 0 else None})
        mine = {**p, **{k: v[4 * i:4 * i + 4] for k, v in experts.items()}}
        y, tally = share._ffn(mine, x)
        parts.append(y - x)
        tallies.append(np.asarray(tally))
    np.testing.assert_allclose(sum(parts), whole, rtol=0,
                               atol=1e-5 * np.abs(whole).max())
    # every pick is held by exactly one share; each counts all pairs
    assert sum(t[:4].sum() for t in tallies) == 23 * 4
    assert all(t.shape == (4 + 1,) and t[-1] == 23 * 4 for t in tallies)
    # and the shared expert is not nothing
    assert np.abs(parts[0] - AttentionBlockRun(**{
        **run.__dict__, "held": (0, 4), "shared_ff": None})._ffn(
            {**p, **{k: v[:4] for k, v in experts.items()}}, x)[0] + x
                  ).max() > 1e-3


def test_the_reached_counter_rides_the_scan_s_one_result(seeded):
    """``generation_server_expert_reached_total`` over ``expert_calls_
    total``: of the held experts' turns, those that got a row.  One
    request of 20 new tokens is a prefill and 20 ticks of one row (a
    tick emits the token its held logits chose and computes the next),
    in 5 scans of 4: a tick reaches exactly top-k = 4 of 16 experts a
    routed layer, the 9-token prefill at most 16; the scheduler moved
    one array a dispatch."""
    net, _, _, _ = seeded
    reg = lambda: drivers.registry_snapshot()["counters"]
    with GenerationServer(net, **SERVER) as srv:
        before = reg()
        srv.submit_async(_prompts([9], seed=7)[0], n_new=20).result(timeout=600)
        after = reg()
    delta = lambda k: after.get(k, 0.0) - before.get(k, 0.0)
    reached = delta("generation_server_expert_reached_total")
    calls = delta("generation_server_expert_calls_total")
    assert calls == (20 + 1) * 4 * 16
    assert 20 * 4 * 4 + 4 * 4 <= reached <= 20 * 4 * 4 + 4 * 16
    rows = delta('generation_server_expert_rows_total{kind="held"}')
    assert rows == (20 + 9) * 4 * 4
    moved = sum(v - before.get(k, 0.0) for k, v in after.items()
                if k.startswith("generation_server_host_transfers_total"))
    sent = sum(v - before.get(k, 0.0) for k, v in after.items()
               if k.startswith("generation_server_dispatches_total"))
    assert moved == sent          # one array a dispatch: in (admit) or out (scan)


# ---------------------------------------------------------------------------
# the family as the harness meets it
# ---------------------------------------------------------------------------
def test_the_family_gives_what_the_harness_asks_for():
    for name in post_ln.REQUIRED:
        assert hasattr(family, name), name
    assert not hasattr(family, "follow_training")
    assert set(family.KERNEL_COSTS) == {"paged_attention", "expert_ffn"}


def test_the_published_shape_and_its_costs_by_hand():
    """``laguna-xs.2``'s file: published widths throughout, depth alone
    reduced; 3.870B parameters; the expert kernel's cost counts the
    experts an even router REACHES at the rows of a call, not all 256."""
    manifest, cell, config = run.cell_files(CELL)
    entry = next(c for c in manifest["configs"] if c["name"] == "laguna-xs.2")
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    pub = config["published"]
    for key, value in pub.items():
        if key != "num_hidden_layers":
            assert config[key] == value, key
    assert (pub["num_hidden_layers"], config["num_hidden_layers"]) == (40, 5)
    c = config["ctor"]
    assert (c["d_model"], c["qk_dim"], c["v_dim"], c["n_kv_heads"],
            c["window_kv_heads"]) == (2048, 128, 128, 8, 8)
    assert (c["n_heads"], c["window_heads"]) == (48, 64)
    assert [c["window_heads"] if x else c["n_heads"]
            for x in c["layer_pattern"]] \
        == pub["num_attention_heads_per_layer"][:5]
    assert [int(t == "sliding_attention") for t in pub["layer_types"][:5]] \
        == c["layer_pattern"]
    assert [int(t == "sparse") for t in pub["mlp_layer_types"][:5]] \
        == c["routed_layers"]
    assert (c["window"], c["n_experts"], c["top_k"], c["expert_ff"],
            c["shared_ff"], c["d_ff"], c["held"], c["vocab_size"]) \
        == (512, 256, 8, 512, 512, 8192, None, 100352)
    full = pub["rope_parameters"]["full_attention"]
    assert c["rotary_dim"] == full["partial_rotary_factor"] * 128
    assert c["window_rotary_dim"] == 128 and c["window_rope_scaling"] is None
    assert {k: full[k] for k in c["rope_scaling"]} == c["rope_scaling"]
    assert c["routed_scale"] == pub["moe_routed_scaling_factor"]
    assert cell["server"] == {"compute_dtype": "bfloat16", "n_slots": 128,
                              "max_len": 4096, "block_size": 128,
                              "tick_batch": 8, "prefix_cache": False}
    t = cell["traffic"]
    assert (t["clients"], t["prompt_len"], t["n_new"]) == (
        128, {"dist": "lognormal", "median": 1024, "sigma": 0.7, "lo": 256,
              "cap": 3072}, {"dist": "uniform", "lo": 128, "hi": 512})

    shape = family.shape_of(config)
    assert shape["layers"] == 5 and shape["held"] == (0, 256)
    d = 2048
    attn = lambda H: d * H * 128 * 2 + 2 * d * 8 * 128 + d * H + 2 * d
    expert = 3 * d * 512
    routed = lambda H: attn(H) + d * 256 + 256 + 257 * expert
    params = (attn(48) + 3 * d * 8192 + 3 * routed(64) + routed(48)
              + 2 * 100352 * d + d)
    assert round(params / 1e9, 3) == 3.870
    specs = ref.leaf_specs(shape)
    counted = (sum(np.prod(s) for s, _ in specs["full_dense"].values())
               + 3 * sum(np.prod(s) for s, _ in specs["win_routed"].values())
               + sum(np.prod(s) for s, _ in specs["full_routed"].values())
               + 2 * 100352 * d + d)
    assert counted == params
    # an even router's reach: 128 rows miss 1.7% of 256 experts, 4 rows 88%
    assert round(family.reached(shape, 128), 1) == 251.6
    assert round(family.reached(shape, 4), 1) == 30.5
    assert family.reached(shape, 4096) > 255.999
    # one decode tick of 128 rows, 4 routed layers = 4 events
    facts = {"prefills": 0.0, "prefill_expert_reads": 0.0,
             "prefill_tokens": 0.0, "decode_tokens": 128.0}
    cost = family.KERNEL_COSTS["expert_ffn"](shape, facts, {}, 4, {})
    pairs = 128 * 4 * 8
    assert cost["flops"] == 6.0 * d * 512 * pairs
    np.testing.assert_allclose(
        cost["bytes"], 2 * (4 * family.reached(shape, 128) * expert
                            + pairs * 2 * d))
    assert cost["bytes"] < 2 * 4 * 256 * expert     # not all 256
    # the paged read: live K/V bytes of both kinds at dh, flops by the
    # kind's own query heads
    facts = {"ctx_sum": 1000.0, "win_ctx_sum": 400.0}
    cost = family.KERNEL_COSTS["paged_attention"](shape, facts, {}, 5, {})
    assert cost["bytes"] == 2 * 256 * 8 * (2 * 1000 + 3 * 400)
    assert cost["flops"] == 2 * 256 * (2 * 48 * 1000 + 3 * 64 * 400)
    work = family.serve_work(shape, [(1000, 1, 3)])
    assert work["prefills"] == 1 and work["decode_tokens"] == 2
    assert work["ctx_sum"] == 1001 + 1002 and work["win_ctx_sum"] == 2 * 512
    assert work["prefill_expert_reads"] > 4 * 255.999


def test_the_lazy_tree_goes_through_seed_tree_and_seed_weights(seeded_bf16):
    """bfloat16 master weights: the program's tree is the reference's,
    leaf for leaf, an expert drawn alone equal to its slice of the
    layer's."""
    net, w, shape = seeded_bf16
    layout = family.layout_of(net)
    assert layout == (("emb",), ("full_dense", 0, 1), ("win_routed", 0, 3),
                      ("full_routed", 0, 1), ("head",))
    back = family.from_program(net.params_tree, layout)
    leaf = w["win_routed"]["W_up"]
    assert isinstance(leaf, ref.ExpertLeaf) and leaf.shape == (3, 16, 32, 16)
    np.testing.assert_array_equal(
        np.asarray(back["win_routed"]["W_up"][2, 5], np.float32),
        leaf.expert(2, 5))
    np.testing.assert_array_equal(
        np.asarray(back["full_routed"]["Ws_gate"][0], np.float32),
        w["full_routed"]["Ws_gate"].layer(0))
    assert {str(a.dtype) for a in jax.tree_util.tree_leaves(net.params_tree)} \
        == {"bfloat16"}
    norms = family.leaf_norms(w)
    assert norms["win_routed.W_gate"].shape == (3,)


REHEARSAL = {"driver": "serve_closed",
             "server": {"compute_dtype": "bfloat16", "n_slots": 4,
                        "max_len": 128, "block_size": 8, "tick_batch": 2,
                        "prefix_cache": False},
             "traffic": {"loop": "closed", "clients": 4,
                         "prompt_len": {"dist": "lognormal", "median": 40,
                                        "sigma": 0.3, "lo": 33, "cap": 60},
                         "n_new": {"dist": "uniform", "lo": 16, "hi": 40},
                         "shared_prefix": 0, "sizes_seed": 0, "n_sizes": 64,
                         "poll_ms": 4, "ramp_seconds": 0.3,
                         "trace_seconds": 0.2, "compare_requests": 3},
             "limits": {"token_gap": 0.55}}


def _stale_ring(monkeypatch):
    """Admissions that do not arm the slot's ring: the slot decodes on
    from the four blocks its last occupant left (or from nothing)."""
    whole = GenerationServer._arm_slot
    monkeypatch.setattr(
        GenerationServer, "_arm_slot",
        lambda self, *a, **kw: whole(self, *a[:12]))


@pytest.mark.parametrize("fault", [None, _stale_ring],
                         ids=["sound", "stale_ring"])
def test_a_cell_of_the_family_runs_and_its_fault_fails(monkeypatch, fault):
    """Through ``run.run_cell`` on the CPU, prompts past one window.
    Sound: token_gap 0.03-0.20 over three seeds (bfloat16 against
    float32; every expert held and the routed sum times 2.5, so a
    flipped pick moves a logit more than in the family that holds a
    share); rings left stale at admission read 1.6-1.9.  The limit 0.55
    is their geometric mean."""
    if fault is not None:
        fault(monkeypatch)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    result = run.run_cell("a-cell-of-the-gated-family", manifest, REHEARSAL,
                          copy.deepcopy(BF16), SEED, 0.5, 0,
                          jax.devices()[:1], {})
    assert result["correct"] is (fault is None), result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["compared"]) == {"token_gap"}


def test_the_control_fails_where_the_program_passes(seeded_bf16):
    """At bfloat16, the served tokens' gap stays under the limit the
    tiny size affords, the float8 control's passes it, and ONE altered
    served token fails ``token_gap``."""
    net, w, shape = seeded_bf16
    prompts = _prompts([45, 38, 50, 33], seed=8)
    with GenerationServer(net, compute_dtype="bfloat16", **SERVER) as srv:
        outs = [srv.submit_async(p, n_new=60).result(timeout=600)
                for p in prompts]
    served = max(family.served_token_gaps(w, shape, out, len(p)).max()
                 for p, out in zip(prompts, outs))
    control = max(family.served_token_gaps(w, shape, out, len(p),
                                           family.CONTROL).max()
                  for p, out in zip(prompts, outs))
    assert served < 0.5 * control, (served, control)
    altered = np.array(outs[0])
    altered[len(prompts[0]) + 20] = (altered[len(prompts[0]) + 20] + 1) % 97
    assert family.served_token_gaps(w, shape, altered, len(prompts[0])).max() \
        > 2 * served


def test_the_new_cell_s_files_are_what_the_manifest_says():
    """The cell and its three metrics are in ``BENCHMARK.json`` under the
    names their files carry; each new metric lists the new cell ALONE,
    its reader is one the harness has, and the program has the counter
    it names."""
    manifest, cell, config = run.cell_files(CELL)
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "laguna-xs.2", "closed-code-context", 1)
    assert cell["config"] == "laguna-xs.2" and cell["driver"] == "serve_closed"
    assert config["family"] == "benchmark.families.gated_sparse_window"
    from benchmark import readers
    for name in ("expert_reached_share", "admit_device_share",
                 "expert_ffn_roofline.all-held"):
        listed = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert listed["workloads"] == [CELL]
        metric = run.load("benchmark", "layer_metrics", name + ".json")
        assert metric["name"] == name and metric["unit"] == listed["unit"]
        assert metric["reader"]["kind"] in readers.READERS
    assert "generation_server_expert_reached_total" in \
        drivers.registry_snapshot()["counters"] or gs._EXPERT_REACHED
    reports = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
               if CELL in m.get("workloads", [CELL])}
    assert {"serve_tokens_per_s", "tpot_p95_ms", "setup_s", "serve_mfu",
            "paged_attention_roofline", "expert_ffn_roofline.all-held",
            "expert_reached_share", "admit_device_share"} <= reports
    assert not {"ttft_p50_ms", "admit_device_ms", "queue_wait_mean_ms",
                "ttft_p95_ms.closed"} & reports
    # tests/benchmark_suite (frozen with the benchmark) pins these three
    # to the cell that holds a SHARE of the experts, alone
    assert not {"expert_ffn_roofline", "expert_rows_mean",
                "expert_load_ratio"} & reports
