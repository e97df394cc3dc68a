"""Speculative multi-token decode: draft-K-ahead + single-dispatch
batched verification must keep greedy output BYTE-IDENTICAL to
non-speculative decode at EVERY acceptance pattern — all-accept (a
full-depth self-draft agrees with the target bitwise), all/mostly-
reject (an independently seeded draft), mid-stream EOS inside an
accepted run, and draft-block-pool exhaustion (a speculative
admission pins ~2x blocks)."""
import time

import numpy as np
import pytest

import jax.numpy as jnp

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.models.generation import TransformerGenerator
from deeplearning4j_tpu.parallel import GenerationServer
from deeplearning4j_tpu.parallel.speculative import (accept_greedy,
                                                     make_draft,
                                                     make_self_draft)
from deeplearning4j_tpu.resilience import FaultInjector
from deeplearning4j_tpu.zoo.gpt import Gpt


def _tiny_gpt(**kw):
    cfg = dict(vocab_size=50, max_len=32, d_model=32, n_layers=2,
               n_heads=4, d_ff=64, seq_len=8, compute_dtype=None,
               seed=3)
    cfg.update(kw)
    return Gpt(**cfg).init_graph()


@pytest.fixture(scope="module")
def net():
    return _tiny_gpt()


@pytest.fixture(scope="module")
def offline(net):
    return TransformerGenerator(net)


# -- acceptance rule (pure host/device math) ---------------------------
def _accept(v, g, rem, eos=None, active=None):
    B = len(v)
    v = jnp.asarray(v, jnp.int32)
    g = jnp.asarray(g, jnp.int32)
    rem = jnp.asarray(rem, jnp.int32)
    eos = jnp.full((B,), -1, jnp.int32) if eos is None \
        else jnp.asarray(eos, jnp.int32)
    active = jnp.ones((B,), bool) if active is None \
        else jnp.asarray(active, bool)
    c, r = accept_greedy(v, g, active, rem, eos)
    return np.asarray(c), np.asarray(r)


def test_accept_greedy_rule():
    # anchor always commits; proposal i commits iff it matches the
    # target's argmax after the previous token AND every earlier
    # proposal matched
    c, r = _accept([[7, 1, 2, 3]], [[1, 2, 3, 9]], [10])
    assert c[0] == 4 and r[0] == 6          # all-accept (+W per round)
    c, r = _accept([[7, 5, 2, 3]], [[1, 2, 3, 9]], [10])
    assert c[0] == 1 and r[0] == 9          # first proposal rejected
    c, r = _accept([[7, 1, 2, 8]], [[1, 2, 3, 9]], [10])
    assert c[0] == 3 and r[0] == 7          # mid mismatch
    # a later "match" behind a mismatch must NOT resurrect the run
    c, r = _accept([[7, 5, 3, 9]], [[1, 2, 3, 9]], [10])
    assert c[0] == 1
    # budget clamp: only `remaining` tokens may commit
    c, r = _accept([[7, 1, 2, 3]], [[1, 2, 3, 9]], [2])
    assert c[0] == 2 and r[0] == 0
    # EOS inside the accepted run cuts it (EOS itself included)
    c, r = _accept([[7, 1, 2, 3]], [[1, 2, 3, 9]], [10], eos=[2])
    assert c[0] == 3 and r[0] == 0
    # EOS at the anchor
    c, r = _accept([[7, 1, 2, 3]], [[1, 2, 3, 9]], [10], eos=[7])
    assert c[0] == 1 and r[0] == 0
    # EOS in the REJECTED suffix does not fire
    c, r = _accept([[7, 1, 8, 3]], [[1, 2, 3, 9]], [10], eos=[3])
    assert c[0] == 2 and r[0] == 8
    # inactive slots commit nothing
    c, r = _accept([[7, 1, 2, 3]], [[1, 2, 3, 9]], [0],
                   active=[False])
    assert c[0] == 0 and r[0] == 0


# -- the bitwise verification contract ---------------------------------
def test_verify_rows_bitwise_equals_sequential_steps(net, offline):
    """The batched W-token verification pass must produce logits AND
    cache writes bitwise identical to W sequential single-token
    decode ticks — the invariant every parity test below rests on
    (flat-row matmuls + per-row-unrolled attention; a naive batched
    score einsum drifts by ulps)."""
    import jax
    gen = offline
    emb_p, blk_ps, head_p = gen._params()
    blk_stack = gen._stack_blocks(blk_ps)
    bs, nb, mb, W = 4, 9, 8, 3
    h = gen.blocks[0].n_heads
    dh = gen.emb.n_out // h
    nl = len(gen.blocks)
    kc = jnp.zeros((nl, nb, h, bs, dh), jnp.float32)
    vc = jnp.zeros((nl, nb, h, bs, dh), jnp.float32)
    table = jnp.asarray([[1, 2, 3, 4, 0, 0, 0, 0],
                         [5, 6, 7, 8, 0, 0, 0, 0]], jnp.int32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 50, 5).astype(np.int32),
               rng.integers(0, 50, 3).astype(np.int32)]
    logits0 = []
    for s, p in enumerate(prompts):
        t0, tb = len(p), 8
        padded = np.zeros((1, tb), np.int32)
        padded[0, :t0] = p
        lg, ks, vs, _ = gen._prefill_rows(emb_p, blk_stack, head_p,
                                          jnp.asarray(padded),
                                          jnp.int32(t0))
        bk = ks[:, 0].reshape(nl, h, tb // bs, bs, dh) \
            .transpose(0, 2, 1, 3, 4)
        bv = vs[:, 0].reshape(nl, h, tb // bs, bs, dh) \
            .transpose(0, 2, 1, 3, 4)
        phys = np.asarray(table[s, :tb // bs])
        kc = kc.at[:, phys].set(bk)
        vc = vc.at[:, phys].set(bv)
        logits0.append(lg[0])
    lg = jnp.stack(logits0)
    pos0 = jnp.asarray([len(p) for p in prompts], jnp.int32)
    # path A: W sequential greedy single-token ticks
    kcA, vcA, posA = kc, vc, pos0
    step = jax.jit(gen._step_paged)
    toks, logitsA = [], []
    for _ in range(W):
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        toks.append(tok)
        wblk = jnp.take_along_axis(table, (posA // bs)[:, None],
                                   axis=1)[:, 0]
        lg, kcA, vcA, _ = step(emb_p, blk_stack, head_p, kcA, vcA, tok,
                               posA, table, wblk, posA % bs)
        logitsA.append(lg)
        posA = posA + 1
    toks = jnp.stack(toks, 1)
    logitsA = jnp.stack(logitsA, 1)
    # path B: ONE batched verification pass over the same tokens
    p = pos0[:, None] + jnp.arange(W)[None, :]
    wblk = jnp.take_along_axis(table, p // bs, axis=1)
    logitsB, kcB, vcB, rec = jax.jit(gen._verify_rows_paged)(
        emb_p, blk_stack, head_p, kc, vc, toks, pos0, p, table,
        wblk, p % bs)
    assert rec is None
    np.testing.assert_array_equal(np.asarray(logitsA),
                                  np.asarray(logitsB))
    np.testing.assert_array_equal(np.asarray(kcA), np.asarray(kcB))
    np.testing.assert_array_equal(np.asarray(vcA), np.asarray(vcB))


# -- end-to-end parity across acceptance patterns ----------------------
def test_spec_parity_all_accept_full_self_draft(net, offline):
    """A full-depth self-draft reads the same params over the same
    context, so every proposal matches the target's argmax bitwise:
    acceptance == proposed, rounds commit K+1 tokens each, and output
    is byte-identical to offline decode."""
    rng = np.random.default_rng(2)
    reqs = [(rng.integers(0, 50, t0).astype(np.int32), n_new)
            for t0, n_new in [(3, 12), (5, 7), (4, 10)]]
    with GenerationServer(net, n_slots=2, max_len=32,
                          tick_timeout_s=None,
                          speculative={"k": 3, "rounds": 2,
                                       "draft_layers": 2}) as srv:
        handles = []
        for prompt, n_new in reqs:
            handles.append(srv.submit_async(prompt, n_new))
        outs = [h.result(timeout=300) for h in handles]
        st = srv.stats()
    for (prompt, n_new), out in zip(reqs, outs):
        np.testing.assert_array_equal(
            out, offline.generate(prompt[None], n_new=n_new)[0])
    assert st["spec_proposed"] > 0
    assert st["spec_accepted"] == st["spec_proposed"]
    assert st["spec_acceptance_rate"] == 1.0


@pytest.mark.slow
def test_spec_parity_reject_heavy_external_draft(net, offline):
    """An independently seeded draft net disagrees with the target
    almost everywhere — the all/mostly-reject pattern: every round
    degrades to ~the anchor token, yet output stays byte-identical
    (the verification recomputes every committed token with the
    target)."""
    draft_net = _tiny_gpt(seed=17)
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, 50, t0).astype(np.int32), n_new)
            for t0, n_new in [(4, 9), (6, 6)]]
    with GenerationServer(net, n_slots=2, max_len=32,
                          tick_timeout_s=None,
                          speculative={"k": 3,
                                       "draft_net": draft_net}) as srv:
        outs = [srv.submit(p, n_new=n, timeout=300) for p, n in reqs]
        st = srv.stats()
    for (prompt, n_new), out in zip(reqs, outs):
        np.testing.assert_array_equal(
            out, offline.generate(prompt[None], n_new=n_new)[0])
    assert st["spec_proposed"] > 0
    # random disagreement: the rate must sit well below full accept
    assert st["spec_accepted"] < st["spec_proposed"]


def test_spec_eos_inside_accepted_draft_run(net, offline):
    """EOS committed MID-chunk (inside an accepted draft run) must cut
    the run at the EOS token exactly as the non-speculative tick's
    hit_eos does — tokens verified behind it are discarded."""
    prompt = np.asarray([5, 9, 2, 7], np.int32)
    ref = offline.generate(prompt[None], n_new=10)[0]
    t0 = len(prompt)
    eos = int(ref[t0 + 3])                   # commits in round 1 of
    first = t0 + int(np.argmax(ref[t0:] == eos))   # a k=5 chunk
    with GenerationServer(net, n_slots=2, max_len=32,
                          tick_timeout_s=None,
                          speculative={"k": 5, "draft_layers": 2}) \
            as srv:
        out = srv.submit(prompt, n_new=10, eos_id=eos, timeout=300)
        st = srv.stats()
    assert out.shape == (first + 1,)
    assert out[-1] == eos
    np.testing.assert_array_equal(out, ref[:first + 1])
    # proposals flushed behind the committed EOS are NOT rejections:
    # the full-depth self-draft stays a perfect 1.0 through EOS cuts
    assert st["spec_proposed"] > 0
    assert st["spec_accepted"] == st["spec_proposed"]


@pytest.mark.slow
def test_spec_draft_block_pool_exhaustion(net, offline):
    """A speculative admission pins target AND draft tables — with a
    pool sized for one such request, the second verifiably queues on
    blocks (a slot is free), completes when the first retires, and
    the allocator is whole afterwards; outputs byte-identical."""
    rng = np.random.default_rng(9)
    reqs = [rng.integers(0, 50, 5).astype(np.int32) for _ in range(2)]
    # one 5+12-token speculative request needs 2*ceil(17/8)=6 blocks
    with GenerationServer(net, n_slots=2, max_len=32, block_size=8,
                          kv_blocks=8, prefix_cache=False,
                          tick_timeout_s=None,
                          speculative={"k": 2, "draft_layers": 1}) \
            as srv:
        srv.submit(reqs[0], n_new=2, timeout=300)   # warm compiles
        with FaultInjector([f"serve_tick_stall@{i}:0.1"
                            for i in range(30)]):
            hs = [srv.submit_async(p, n_new=12) for p in reqs]
            deadline = time.monotonic() + 60
            seen_wait = False
            while time.monotonic() < deadline:
                with srv._lock:
                    n_act, n_pend = len(srv._active), len(srv._pending)
                if n_act == 1 and n_pend == 1 and hs[0].emitted > 0:
                    seen_wait = True
                    break
                time.sleep(0.005)
            assert seen_wait
            outs = [h.result(timeout=300) for h in hs]
        with srv._lock:
            assert int(srv._block_ref[1:].max(initial=0)) == 0
            assert len(srv._blocks_free) == srv.kv_blocks
    for p, out in zip(reqs, outs):
        np.testing.assert_array_equal(
            out, offline.generate(p[None], n_new=12)[0])


def test_spec_mixed_pool_speculates_and_greedy_stays_exact(net,
                                                           offline):
    """A sampled slot SPECULATES (rejection resampling, ISSUE 20)
    instead of dropping the pool to the plain scan: the greedy
    neighbour in the same ``lax.scan`` tick stays byte-identical to
    offline decode through the flat-row verify path, the sampled
    request stays in-range and reproducible per seed, and the rounds
    actually ran while the sampled slot was live."""
    pg = np.asarray([4, 5, 6], np.int32)
    ps = np.asarray([1, 2, 3], np.int32)
    samp = {"temperature": 1.0, "top_k": 5, "seed": 11}
    with GenerationServer(net, n_slots=2, max_len=32,
                          tick_timeout_s=None,
                          speculative={"k": 3, "draft_layers": 2}) \
            as srv:
        p0 = srv.stats()["spec_proposed"]
        hg = srv.submit_async(pg, n_new=8)
        hs = srv.submit_async(ps, n_new=8, sampling=dict(samp))
        np.testing.assert_array_equal(
            hg.result(timeout=300),
            offline.generate(pg[None], n_new=8)[0])
        out_s = hs.result(timeout=300)
        # speculation ran THROUGH the mixed pool, not after it
        assert srv.stats()["spec_proposed"] > p0
        assert out_s.shape == (11,)
        assert (out_s >= 0).all() and (out_s < 50).all()
    # same seed on a fresh server: byte-identical sampled stream
    with GenerationServer(net, n_slots=2, max_len=32,
                          tick_timeout_s=None,
                          speculative={"k": 3, "draft_layers": 2}) \
            as srv:
        np.testing.assert_array_equal(
            srv.submit(ps, n_new=8, sampling=dict(samp), timeout=300),
            out_s)


def test_spec_prefix_cache_hit_parity(net, offline):
    """Shared-prefix admission on a speculative server: the second
    same-prompt request rides the target's prefix-cache HIT path AND
    the draft's (ISSUE 20 — draft blocks chain-hash and reuse like
    target blocks) — both then decode speculatively, byte-identical
    to offline."""
    reg = telemetry.get_registry()
    hits = reg.counter("prefix_cache_hits_total")
    p = np.arange(1, 14, dtype=np.int32)     # 3 full blocks @ bs=4
    ref = offline.generate(p[None], n_new=6)[0]
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          tick_timeout_s=None,
                          speculative={"k": 2, "draft_layers": 2}) \
            as srv:
        h0 = hits.value
        np.testing.assert_array_equal(
            srv.submit(p, n_new=6, timeout=300), ref)
        with srv._lock:
            # the retire registered the draft chain too
            assert len(srv._dprefix_map) == 3
            assert len(srv._draft_cached) == 3
        np.testing.assert_array_equal(
            srv.submit(p, n_new=6, timeout=300), ref)
        assert hits.value - h0 == 1
        with srv._lock:
            # the second admission compiled/ran the draft-HIT program
            # (cache key: ("hit", sb, matched, dtb, nfill, use_draft,
            # dmatched, dsb) with dmatched > 0)
            assert any(k[0] == "hit" and k[6] > 0
                       for k in srv._admit_cache)
        assert srv.stats()["spec_accepted"] \
            == srv.stats()["spec_proposed"]


def test_spec_round_moves_one_array_each_way(net, offline,
                                             guarded_scheduler):
    """ISSUE 32 on the speculative path, the scheduler's thread under
    ``jax.transfer_guard``: a round's result (tokens, ``emitted``,
    ``remaining``, the per-slot proposed and accepted tallies) is ONE
    read at the one poll site, its per-slot depths ONE array in; a
    miss's and a draft-cache hit's operands — the draft's rows among
    them — ONE vector each.  Tokens and acceptance as without the
    guard."""
    p = np.arange(1, 14, dtype=np.int32)     # 3 full blocks @ bs=4
    ref = offline.generate(p[None], n_new=6)[0]
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          tick_timeout_s=None,
                          speculative={"k": 2, "draft_layers": 2}) \
            as srv:
        before = guarded_scheduler.counts()
        for _ in range(2):                   # a miss, then the hit
            np.testing.assert_array_equal(
                srv.submit(p, n_new=6, timeout=300), ref)
        d = guarded_scheduler.held(before)
        st = srv.stats()
    assert d["admitted_total"] == 2
    assert d['host_transfers_total{site="scan",dir="h2d"}'] \
        == d['dispatches_total{program="scan"}']
    assert st["spec_accepted"] == st["spec_proposed"] > 0


def test_spec_on_the_kernel_route_reads_a_lane_wide_pool(net, offline,
                                                        monkeypatch):
    """The speculative programs on the kernel route (forced; Pallas
    interpret mode here) still scatter, then read — into and from a
    pool of whole 128-lane rows, the net's four 8-wide heads side by
    side in one (ISSUE 34: the read-only kernels take four query rows a
    position a pool head there): a request, then the
    same prompt down both prefix-HIT paths, equal to offline decode."""
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", "pallas")
    p = np.arange(1, 14, dtype=np.int32)     # 3 full blocks @ bs=4
    ref = offline.generate(p[None], n_new=6)[0]
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          tick_timeout_s=None,
                          speculative={"k": 2, "draft_layers": 2}) \
            as srv:
        assert srv._kc.shape[2:] == srv._vc.shape[2:] == (1, 4, 128)
        np.testing.assert_array_equal(
            srv.submit(p, n_new=6, timeout=600), ref)
        np.testing.assert_array_equal(
            srv.submit(p, n_new=6, timeout=600), ref)
        assert srv.stats()["spec_proposed"] > 0


def test_spec_fleet_passthrough_and_stats(net, offline):
    """``speculative=`` flows through ServingFleet's server_kwargs to
    every replica; per-replica acceptance/spec_k surface in
    ``fleet.stats()`` (the spec-aware view dispatch reads) and routed
    requests stay byte-identical to offline decode."""
    from deeplearning4j_tpu.serving import ServingFleet
    p = np.asarray([3, 1, 4, 1, 5], np.int32)
    ref = offline.generate(p[None], n_new=6)[0]
    with ServingFleet(net, n_replicas=2, n_slots=2, max_len=32,
                      tick_batch=1, tick_timeout_s=None,
                      speculative={"k": 2, "rounds": 2,
                                   "draft_layers": 2}) as fleet:
        np.testing.assert_array_equal(
            fleet.submit(p, n_new=6, timeout=300), ref)
        st = fleet.stats()
    assert all(r["spec_k"] == 2 for r in st["replicas"])
    served = [r for r in st["replicas"] if r["spec_proposed"] > 0]
    assert served and all(r["spec_accepted"] == r["spec_proposed"]
                          for r in served)   # full-depth self-draft


def test_spec_validation(net):
    with pytest.raises(ValueError, match="speculative k"):
        GenerationServer(net, n_slots=1, speculative={"k": 0})
    with pytest.raises(ValueError, match="rounds"):
        GenerationServer(net, n_slots=1,
                         speculative={"k": 2, "rounds": 0})
    with pytest.raises(ValueError, match="draft_layers"):
        GenerationServer(net, n_slots=1,
                         speculative={"draft_layers": 3})
    with pytest.raises(ValueError, match="unknown speculative"):
        GenerationServer(net, n_slots=1, speculative={"K": 2})
    with pytest.raises(ValueError, match="k_max"):
        GenerationServer(net, n_slots=1,
                         speculative={"k": 3, "k_max": 2})
    with pytest.raises(ValueError, match="kv_blocks"):
        # 2 blocks of 16 hold one max-length TARGET table only — the
        # draft table doubles the floor
        GenerationServer(net, n_slots=1, max_len=32, block_size=16,
                         kv_blocks=2, speculative={"k": 2})
    # external-draft geometry gates
    gen = TransformerGenerator(net)
    with pytest.raises(ValueError, match="draft depth"):
        make_draft(gen, _tiny_gpt(n_layers=3))
    with pytest.raises(ValueError, match="n_heads"):
        make_draft(gen, _tiny_gpt(n_heads=2))
    with pytest.raises(ValueError, match="vocab"):
        make_draft(gen, _tiny_gpt(vocab_size=49))
    with pytest.raises(ValueError, match="draft_layers applies"):
        GenerationServer(net, n_slots=1, speculative={
            "draft_net": _tiny_gpt(seed=17), "draft_layers": 1})
    assert make_self_draft(gen).n_layers == 1   # default: half stack


@pytest.mark.slow
def test_spec_recovery_salvages_draft_table(net, offline):
    """A forced watchdog-style recovery mid-decode on a speculative
    server must salvage the slot's TARGET and DRAFT tables together
    (the dtable state leaf rides the block-granular salvage) — the
    request completes without resubmission, byte-identical, and the
    allocator drains both tables' blocks at retire."""
    p = np.arange(1, 10, dtype=np.int32)
    ref = offline.generate(p[None], n_new=16)[0]
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          tick_timeout_s=None,
                          speculative={"k": 2, "rounds": 1,
                                       "draft_layers": 2}) as srv:
        srv.submit(p, n_new=2, timeout=300)       # warm the compiles
        with FaultInjector(["serve_tick_stall@0:0.3",
                            "serve_tick_stall@1:1.5"]):
            h = srv.submit_async(p, n_new=16)
            deadline = time.monotonic() + 60
            while h.emitted == 0 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert h.emitted > 0
            time.sleep(0.1)       # inside the pre-dispatch stall: the
            srv._recover("test-forced recovery")   # pool is committed
            out = h.result(timeout=300)
        np.testing.assert_array_equal(out, ref)
        with srv._lock:
            assert int(srv._block_ref[1:].max(initial=0)) == 0


@pytest.mark.slow
def test_spec_soak_staggered_mixed_patterns(net, offline):
    """Soak: 10 staggered mixed-budget requests (some EOS, one
    cancel) through a truncated self-draft server with a tight pool —
    constant accept/reject churn, rollback, block exhaustion waits —
    every greedy output byte-identical to offline decode."""
    from deeplearning4j_tpu.resilience import CancelledError
    rng = np.random.default_rng(5)
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          kv_blocks=20, tick_timeout_s=None,
                          speculative={"k": 4, "rounds": 4,
                                       "draft_layers": 1}) as srv:
        reqs, handles = [], []
        for i in range(10):
            t0 = int(rng.integers(3, 8))
            n_new = int(rng.integers(4, 24 - t0))
            p = rng.integers(0, 50, t0).astype(np.int32)
            reqs.append((p, n_new))
            handles.append(srv.submit_async(p, n_new=n_new))
            if i % 3 == 0:
                time.sleep(0.01)
        h_cancel = srv.submit_async(np.asarray([1, 2, 3], np.int32),
                                    n_new=20)
        assert h_cancel.cancel() is True
        for (p, n_new), h in zip(reqs, handles):
            np.testing.assert_array_equal(
                h.result(timeout=300),
                offline.generate(p[None], n_new=n_new)[0])
        with pytest.raises(CancelledError):
            h_cancel.result(timeout=300)
        with srv._lock:
            assert int(srv._block_ref[1:].max(initial=0)) == 0
