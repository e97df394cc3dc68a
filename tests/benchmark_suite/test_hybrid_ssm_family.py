"""The hybrid state-space / attention family (``benchmark/families/
hybrid_ssm.py``) and the program it drives, at a tiny size on the CPU:
8 layers (attention at ``i % 4 == 2``), d 64, 4 query heads on 1 K/V
head of 16, d_state 16, d_conv 4, dt_rank 4, ff 128, vocab 97.

Weights are the family's seeded ones at ``init_std`` 0.1 (N(0, 0.02)
at these widths leaves a layer's output far below the embedding, and
every context then gives the same token).  Nothing here is a
measurement."""
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
from benchmark.families import hybrid_ssm, post_ln  # noqa: E402
from benchmark.families import hybrid_ssm_reference as ref  # noqa: E402
from deeplearning4j_tpu.models.generation import TransformerGenerator  # noqa: E402
from deeplearning4j_tpu.parallel import GenerationServer  # noqa: E402
from deeplearning4j_tpu.parallel import generation_server as gs  # noqa: E402

TINY = dict(vocab_size=97, d_model=64, n_layers=8, d_ff=128, n_heads=4,
            n_kv_heads=1, attn_period=4, attn_offset=2, d_state=16, d_conv=4,
            expand=2, dt_rank=4, seq_len=16)
SEED = 2 ** 31 + 23
F32 = {"family": "benchmark.families.hybrid_ssm", "init_std": 0.1,
       "zoo_class": "deeplearning4j_tpu.zoo.hybrid_decoder.HybridDecoder",
       "ctor": dict(TINY, compute_dtype=None)}
BF16 = dict(F32, ctor=dict(TINY, compute_dtype="bfloat16", dtype="bfloat16"),
            precision={"master_weights": "bfloat16"})
SERVER = {"n_slots": 3, "max_len": 64, "block_size": 8, "tick_batch": 4,
          "prefix_cache": False}


@pytest.fixture(scope="module")
def seeded():
    """(net, the reference's tree, shape, offline generator) in float32."""
    shape = hybrid_ssm.shape_of(F32)
    net = drivers.build_net(F32)
    drivers.seed_weights(net, hybrid_ssm, shape, SEED)
    w = drivers.seed_tree(hybrid_ssm, shape, hybrid_ssm.seed_key(SEED))
    return net, w, shape, TransformerGenerator(net)


@pytest.fixture(scope="module")
def seeded_bf16():
    """(net, the reference's tree, shape): bfloat16 master weights."""
    shape = hybrid_ssm.shape_of(BF16)
    net = drivers.build_net(BF16)
    init_dtypes = {str(a.dtype) for a in jax.tree_util.tree_leaves(net.params_tree)}
    drivers.seed_weights(net, hybrid_ssm, shape, SEED, "bfloat16")
    w = drivers.seed_tree(hybrid_ssm, shape, hybrid_ssm.seed_key(SEED), "bfloat16")
    return net, w, shape, init_dtypes


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
            for n in lengths]


def _close(program, reference):
    """Within 1e-4 of the largest logit's magnitude: float32 on both
    sides, the same equations in another order of operations (the
    program batches, caches and scans; the reference does not)."""
    reference = np.asarray(reference)
    np.testing.assert_allclose(np.asarray(program), reference, rtol=0,
                               atol=1e-4 * np.abs(reference).max())


# ---------------------------------------------------------------------------
# the program against the reference, float32
# ---------------------------------------------------------------------------
def test_full_forward_equals_the_reference(seeded):
    net, w, shape, _ = seeded
    ids = _prompts([40])[0]
    _close(net.output(ids[None])[0], ref.lm_logits(w, shape, ids)[None])


def test_offline_prefill_then_decode_equals_the_reference(seeded):
    """Teacher-forced: the prompt's prefill, then one cached step per
    later token, give the reference's logits at every served position;
    and ``generate()`` picks the reference's tokens."""
    net, w, shape, gen = seeded
    ids = _prompts([30])[0]
    t0 = 11
    emb_p, blk_ps, head_p = gen._params()
    runs_p = gen._stack_blocks(blk_ps)
    logits, ks, vs, rec = jax.jit(gen._prefill_rows)(
        emb_p, runs_p, head_p, ids[None, :t0])
    pad = ((0, 0), (0, 0), (0, 0), (0, len(ids) - t0), (0, 0))
    kc, vc = jnp.pad(ks, pad), jnp.pad(vs, pad)
    got, step = [logits[0]], jax.jit(gen._step)
    for pos in range(t0, len(ids) - 1):
        logits, kc, vc, rec = step(emb_p, runs_p, head_p, kc, vc, rec,
                                   jnp.asarray(ids[pos:pos + 1]), pos)
        got.append(logits[0])
    _close(jnp.stack(got), ref.lm_logits(w, shape, ids)[None][t0 - 1:-1])
    out = gen.generate(ids[None, :t0], 20)[0]
    assert hybrid_ssm.served_token_gaps(w, shape, out, t0).max() == 0.0


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
    decode scan over the paged pool and the slot's recurrent state:
    the reference's logits at every served position, in a slot that is
    not the first."""
    net, w, shape, _ = seeded
    ids = _prompts([30], seed=3)[0]
    t0 = 11
    with GenerationServer(net, **SERVER) as srv:
        got = _drive_by_hand(srv, ids[:t0], 1, ids[t0:-1])
        # the other slots' state was never touched
        assert not np.asarray(srv._state["rec_h"][:, [0, 2]]).any()
        assert np.asarray(srv._state["rec_h"][:, 1]).any()
    _close(got, ref.lm_logits(w, shape, ids)[None][t0 - 1:-1])


def test_a_padded_bucket_leaves_the_state_as_after_the_last_real_token(seeded):
    """A 9-token prompt prefilled alone, in a bucket of 16 and in one of
    32: the same logits, the same recurrent state and the same K/V rows
    -- pad positions advance nothing."""
    net, _, _, gen = seeded
    prompt = _prompts([9], seed=5)[0]
    emb_p, blk_ps, head_p = gen._params()
    runs_p = gen._stack_blocks(blk_ps)
    alone = jax.jit(gen._prefill_rows)(emb_p, runs_p, head_p, prompt[None])
    for bucket in (16, 32):
        padded = np.full((1, bucket), 7, np.int32)      # pad with a live id
        padded[0, :9] = prompt
        logits, ks, vs, rec = jax.jit(gen._prefill_rows)(
            emb_p, runs_p, head_p, padded, jnp.int32(9))
        # float32 round-off of another trip count, not a state one step on
        tol = dict(rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(logits, alone[0], **tol)
        np.testing.assert_allclose(ks[:, :, :, :9], alone[1], **tol)
        np.testing.assert_allclose(rec["h"], alone[3]["h"], **tol)
        np.testing.assert_allclose(rec["conv"], alone[3]["conv"], **tol)


def test_requests_in_flight_together_each_equal_their_solo_run(seeded):
    """Three requests of different lengths on three slots, budgets 3, 9
    and 14 at scans of 4 ticks (one retires mid-scan), a fourth that
    takes over a freed slot: every one equals its solo run (alone in
    the server, afterwards) and the reference's greedy choice."""
    net, w, shape, _ = seeded
    prompts = _prompts([7, 13, 5, 20], seed=1)
    budgets = [3, 9, 14, 11]
    with GenerationServer(net, **SERVER) as srv:
        outs = [h.result(timeout=300) for h in
                [srv.submit_async(p, n_new=n) for p, n in zip(prompts, budgets)]]
        solo = [srv.submit_async(p, n_new=n).result(timeout=300)
                for p, n in zip(prompts, budgets)]
    for p, out, alone in zip(prompts, outs, solo):
        np.testing.assert_array_equal(out, alone)
        assert hybrid_ssm.served_token_gaps(w, shape, out, len(p)).max() == 0.0


def test_the_kernel_route_serves_the_same_tokens(seeded, monkeypatch):
    """Both kernels in interpret mode inside the server's own programs
    (the pool and the stacked state aliased through them, a layer picked
    by a traced index): the tokens of the ``jax.numpy`` routes."""
    net, _, _, gen = seeded
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", "pallas")
    monkeypatch.setenv("DL4J_TPU_SSM_KERNEL", "pallas")
    prompts = _prompts([6, 11], seed=2)
    with GenerationServer(net, **dict(SERVER, tick_batch=2)) as srv:
        assert srv._kc.shape[-1] == 128          # the kernel route's pool
        outs = [h.result(timeout=600) for h in
                [srv.submit_async(p, n_new=5) for p in prompts]]
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, gen.generate(p[None], 5)[0])


# ---------------------------------------------------------------------------
# the two kernels against their jax.numpy routes
# ---------------------------------------------------------------------------
def test_ssm_step_kernel_equals_its_reference_route():
    """Interpret mode; float32 sums in another order: 1e-5.  Inactive
    slots and other layers keep their state bit for bit."""
    mod = importlib.import_module("deeplearning4j_tpu.kernels.ssm_step")
    L, B, n, di = 3, 16, 16, 256
    ks = jax.random.split(jax.random.PRNGKey(0), 9)
    h = jax.random.normal(ks[0], (L, B, n, di), jnp.float32)
    args = dict(
        h=h, layer=jnp.int32(1), dt=jax.random.normal(ks[1], (B, di)),
        u=jax.random.normal(ks[2], (B, di)), b=jax.random.normal(ks[3], (B, n)),
        c=jax.random.normal(ks[4], (B, n)), z=jax.random.normal(ks[5], (B, di)),
        a=-jnp.exp(jax.random.normal(ks[6], (n, di))),
        d=jax.random.normal(ks[7], (di,)), dt_bias=jax.random.normal(ks[8], (di,)),
        active=jnp.arange(B) % 3 != 0)
    want_out, want_h = mod.ssm_step_reference(**args)
    out, got_h = jax.jit(mod._ssm_step_pallas)(**args)
    np.testing.assert_allclose(out, want_out, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_h, want_h, rtol=1e-5, atol=1e-5)
    h = np.asarray(h)
    for got in (np.asarray(got_h), np.asarray(want_h)):
        np.testing.assert_array_equal(got[[0, 2]], h[[0, 2]])
        np.testing.assert_array_equal(got[1, ::3], h[1, ::3])
        assert not np.array_equal(got[1, 1], h[1, 1])


def test_grouped_paged_attention_kernel_equals_the_reference_route():
    """Four query heads on one K/V head, the decode scan's kernel in
    interpret mode against scatter + the gather reference: 1e-5 (an
    online softmax against a whole one); the written row lands in the
    right layer and nowhere else."""
    mod = importlib.import_module("deeplearning4j_tpu.kernels.paged_attention")
    L, B, hq, hkv, dh, bs, mb, nb = 2, 3, 4, 1, 16, 8, 4, 13
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    kp = jax.random.normal(ks[0], (L, nb, hkv, bs, dh))
    vp = jax.random.normal(ks[1], (L, nb, hkv, bs, dh))
    q = jax.random.normal(ks[2], (B, hq, dh))
    kn, vn = (jax.random.normal(k, (B, hkv, dh)) for k in ks[3:])
    table = jnp.asarray(1 + np.arange(B * mb).reshape(B, mb), jnp.int32)
    pos = jnp.asarray([3, 17, 30], jnp.int32)
    wblk = jnp.take_along_axis(table, (pos // bs)[:, None], 1)[:, 0]
    woff = pos % bs
    att, ko, vo = mod._paged_decode_write_pallas(
        q, kn, vn, kp, vp, table, pos, wblk, woff, jnp.int32(1), dh ** -0.5)
    kl = kp[1].at[wblk, :, woff, :].set(kn)
    vl = vp[1].at[wblk, :, woff, :].set(vn)
    want = mod.paged_decode_attention_reference(q, kl, vl, table, pos, dh ** -0.5)
    np.testing.assert_allclose(att, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(ko[1], kl)
    np.testing.assert_array_equal(vo[0], vp[0])
    # the read-only kernel walks one query head a K/V head: it says so
    monkey = pytest.MonkeyPatch()
    monkey.setattr(mod, "_route", lambda: "pallas")
    try:
        with pytest.raises(ValueError, match="grouped query heads"):
            mod.paged_decode_attention(q, kl, vl, table, pos)
    finally:
        monkey.undo()


# ---------------------------------------------------------------------------
# what a net with recurrent layers refuses, each by its message
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kw, what", [
    ({"prefix_cache": True}, "prefix_cache=True"),
    ({"speculative": {"k": 2}}, "speculative decode"),
    ({"host_tier_blocks": 4}, "host_tier_blocks > 0"),
    ({"tp": 2}, "tp > 1"),
    ({"devices": 2}, "tp > 1")],
    ids=["prefix_cache", "speculative", "host_tier", "tp", "two_devices"])
def test_a_recurrent_net_refuses_at_construction(seeded, kw, what):
    net = seeded[0]
    if "devices" in kw:
        kw = {"devices": jax.devices()[:2]}
    with pytest.raises(ValueError, match=f"{what} is not supported for a net "
                                         "with recurrent"):
        GenerationServer(net, **dict(SERVER, **kw))


@pytest.mark.parametrize("call", ["export_prefix", "import_blocks",
                                  "prefill_async"])
def test_a_recurrent_net_refuses_the_hand_off_calls(seeded, call):
    with GenerationServer(seeded[0], **SERVER) as srv:
        with pytest.raises(ValueError, match=f"{call} is not supported for a "
                                             "net with recurrent.*cannot restore"):
            getattr(srv, call)(np.arange(9, dtype=np.int32))
        assert srv.stats()["live_slots"] == 0


# ---------------------------------------------------------------------------
# the family seam
# ---------------------------------------------------------------------------
def test_the_family_gives_what_the_harness_asks_for():
    missing = [a for a in post_ln.REQUIRED if not hasattr(hybrid_ssm, a)]
    assert not missing, missing
    assert not hasattr(hybrid_ssm, "follow_training")
    assert set(hybrid_ssm.KERNEL_COSTS) == {"paged_attention", "ssm_step"}
    assert drivers.family_of(F32) is hybrid_ssm
    shape = hybrid_ssm.shape_of(F32)
    assert (shape["layers"], shape["ssm_layers"], shape["attn_layers"],
            shape["vocab"]) == (8, 6, 2, 97)
    assert [k for k, _ in ref.layer_kinds(shape)] == [
        "mamba", "mamba", "attn", "mamba", "mamba", "mamba", "attn", "mamba"]


def test_the_published_shape_and_its_costs_by_hand():
    """The configuration file's sizes: 28 layers of which 2 attend, the
    parameter count of ISSUE 28, and the two kernels' costs for one
    token at context 100."""
    with open(os.path.join(ROOT, "benchmark", "configs", "jamba2-3b.json")) as f:
        config = json.load(f)
    shape = hybrid_ssm.shape_of(config)
    assert (shape["layers"], shape["ssm_layers"], shape["attn_layers"],
            shape["vocab"], shape["d_inner"], shape["dt_rank"],
            shape["head_dim"]) == (28, 26, 2, 65536, 5120, 160, 128)
    assert [i for i, (k, _) in enumerate(ref.layer_kinds(shape))
            if k == "attn"] == [7, 21]
    mm = hybrid_ssm.matmul_params(shape)
    assert mm == 26 * 41_123_840 + 2 * 13_762_560 + 28 * 62_914_560
    leaves = sum(int(np.prod(inner)) * n for group, n in (("mamba", 26), ("attn", 2))
                 for inner, _ in ref.leaf_specs(shape)[group].values())
    assert round((leaves + 65536 * 2560 + 2560) / 1e9, 2) == 3.03
    work = hybrid_ssm.serve_work(shape, [(100, 1, 3)])
    assert work["decode_tokens"] == 2 and work["ctx_sum"] == 101 + 102
    facts = {"ctx_sum": 100.0, "decode_tokens": 1.0}
    paged = hybrid_ssm.KERNEL_COSTS["paged_attention"](shape, facts, {}, 0, {})
    assert paged == {"flops": 4.0 * 2 * 2560 * 100, "bytes": 2.0 * 2 * 128 * 2 * 100}
    ssm = hybrid_ssm.KERNEL_COSTS["ssm_step"](shape, facts, {}, 0, {})
    assert ssm["bytes"] == 26 * (2 * 5120 * 16 * 4 + 5120 * 10 + 128)
    assert ssm["flops"] == 26 * 9.0 * 5120 * 16


def test_the_lazy_tree_goes_through_seed_tree_and_seed_weights(seeded_bf16):
    """bfloat16 master weights: ``seed_tree`` only records the rounding
    (no leaf is drawn), the program's leaves are bfloat16 in the
    program's layout, and the reference's layer weights are the same
    rounded values in float32."""
    net, w, shape, init_dtypes = seeded_bf16
    key = hybrid_ssm.seed_key(SEED)
    leaves = jax.tree_util.tree_leaves(w)
    assert leaves and all(isinstance(a, ref.Leaf) for a in leaves)
    assert {tuple(str(c) for c in a.casts) for a in leaves} == {
        ("bfloat16", "float32")}
    assert init_dtypes == {"bfloat16"}    # init() too: the ctor's ``dtype``
    tree = net.params_tree
    assert {str(a.dtype) for a in jax.tree_util.tree_leaves(tree)} == {"bfloat16"}
    # run 3 of the net is Mamba layers 2..4 of the reference
    assert hybrid_ssm.layout_of(net)[3] == ("mamba", 2, 3)
    layer3 = ref.layer_weights(w["mamba"], 3)
    for name, a in layer3.items():
        assert a.dtype == jnp.float32
        got = np.asarray(tree["layer_3"][name][1].astype(jnp.float32))
        np.testing.assert_array_equal(
            got, np.asarray(a).T if name in ("conv_w", "A_log") else np.asarray(a))
    exact = ref.layer_weights(hybrid_ssm.weights_from_key(shape, key)["mamba"], 3)
    assert not np.array_equal(exact["W_in"], layer3["W_in"])
    back = hybrid_ssm.from_program(tree, hybrid_ssm.layout_of(net))
    assert back["mamba"]["A_log"].shape == (6, 128, 16)
    norms = hybrid_ssm.leaf_norms(back)
    assert len(norms) == 17 + 9 + 2 and norms["mamba.W_in"].shape == (6,)
    # the recurrence starts as the Mamba paper starts it
    a_log = np.asarray(layer3["A_log"])
    assert np.abs(a_log - np.log(np.arange(1, 17))).max() < 0.6
    step = np.asarray(jax.nn.softplus(layer3["dt_bias"]))
    assert 5e-4 < step.min() and step.max() < 0.2


def test_one_copy_of_the_weights(seeded):
    """The server's snapshot of a stack of runs IS the net's tree: no
    leaf is stacked or copied."""
    net = seeded[0]
    with GenerationServer(net, **SERVER) as srv:
        emb_p, runs_p, head_p = srv._params
        assert emb_p["W"] is net.params_tree["layer_0"]["W"]
        for i, p in enumerate(runs_p, start=1):
            for name, a in p.items():
                assert a is net.params_tree[f"layer_{i}"][name], (i, name)
        assert "W" not in head_p                  # the head reads the table
        scrape = drivers.registry_snapshot()
        assert scrape["gauges"]["generation_server_recurrent_state_bytes"] == (
            6 * 3 * (16 * 128 * 4 + 3 * 128 * 4))


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


def _stale_state(monkeypatch):
    """Admissions that do not arm the slot's recurrent state: the slot
    decodes on from what its last occupant left (or from nothing)."""
    whole = GenerationServer._arm_slot
    monkeypatch.setattr(
        GenerationServer, "_arm_slot", lambda self, *a, **kw: whole(self, *a[:12]))


@pytest.mark.parametrize("fault", [None, _stale_state], ids=["sound", "stale_state"])
def test_a_cell_of_the_family_runs_and_its_fault_fails(monkeypatch, fault):
    """Sound, on this seed and three others: token_gap at most 0.05
    (bfloat16 against float32); a state left stale at admission reads
    1.1-2.9.  The limit is the accepted serve cell's, 0.12."""
    if fault is not None:
        fault(monkeypatch)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    result = run.run_cell("a-cell-of-the-hybrid-family", manifest, CELL,
                          copy.deepcopy(BF16), SEED, 0.5, 0, jax.devices()[:1], {})
    assert result["correct"] is (fault is None), result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["compared"]) == {"token_gap"}


def test_the_control_fails_where_the_program_passes(seeded_bf16):
    """bfloat16 program, offline: its tokens lie within 0.12 of the
    reference's best on every prompt; the control's (float8 operands
    and a bfloat16 state: one precision below on both counts) do not.
    The state lowered alone is read too: at these widths it moves no
    token (PERF.md has the published widths' reading)."""
    net, w, shape, _ = seeded_bf16
    gen = TransformerGenerator(net, compute_dtype="bfloat16")
    worst = {None: 0.0, "state_bf16": 0.0, hybrid_ssm.CONTROL: 0.0}
    for prompt in _prompts([13, 13, 13], seed=4):
        out = gen.generate(prompt[None], 24)[0]
        for quant in worst:
            gaps = hybrid_ssm.served_token_gaps(w, shape, out, len(prompt), quant)
            worst[quant] = max(worst[quant], float(gaps.max()))
    assert worst[None] < 0.12 < worst[hybrid_ssm.CONTROL], worst
    assert worst["state_bf16"] <= worst[hybrid_ssm.CONTROL]
    with pytest.raises(ValueError, match="unknown control precision"):
        hybrid_ssm.served_token_gaps(w, shape, out, len(prompt), "fp4")


def test_a_train_cell_of_the_family_fails_plainly():
    with pytest.raises(SystemExit, match="no training reference"):
        run.run_cell("a-train-cell", {"per_layer": []}, {"driver": "train"},
                     F32, SEED, 0.5, 0, [], {})


def test_the_prefill_counters_tell_real_from_pad(seeded):
    """A 9-token prompt goes out in a bucket of 16: 9 real, 7 pad."""
    before = drivers.registry_snapshot()["counters"]
    with GenerationServer(seeded[0], **SERVER) as srv:
        srv.submit_async(_prompts([9])[0], n_new=2).result(timeout=300)
    after = drivers.registry_snapshot()["counters"]
    name = 'generation_server_prefill_tokens_total{kind="%s"}'
    assert after[name % "real"] - before.get(name % "real", 0) == 9
    assert after[name % "pad"] - before.get(name % "pad", 0) == 7
