"""Continuous-batching decode server: greedy outputs through slot
scheduling must be BYTE-IDENTICAL to offline ``generate()`` per
request — including requests that join mid-flight (staggered
admission, mixed n_new), queue behind a full slot pool, or retire
early on EOS."""
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.models.generation import TransformerGenerator
from deeplearning4j_tpu.parallel import GenerationServer
from deeplearning4j_tpu.resilience import CancelledError, FaultInjector
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


def test_greedy_parity_staggered_mixed_n_new(net, offline):
    """5 requests with different prompt lengths and budgets through a
    2-slot pool: admissions necessarily interleave with other slots
    mid-decode, and every result must equal the offline decode."""
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, 50, t0).astype(np.int32), n_new)
            for t0, n_new in [(3, 6), (4, 4), (5, 9), (7, 3), (6, 12)]]
    with GenerationServer(net, n_slots=2, max_len=32) as srv:
        handles = []
        for prompt, n_new in reqs:
            handles.append(srv.submit_async(prompt, n_new))
            time.sleep(0.01)            # stagger admissions
        outs = [h.result(timeout=300) for h in handles]
    for (prompt, n_new), out in zip(reqs, outs):
        ref = offline.generate(prompt[None], n_new=n_new)[0]
        np.testing.assert_array_equal(out, ref)
        assert out.shape == (len(prompt) + n_new,)


def test_slot_exhaustion_queues_and_completes(net, offline):
    """More requests than slots: the overflow waits in the queue, gets
    the freed slot, and still decodes exactly."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 50, 4).astype(np.int32) for _ in range(3)]
    retired = telemetry.get_registry().counter(
        "generation_server_retired_total")
    before = retired.value
    with GenerationServer(net, n_slots=1, max_len=32) as srv:
        handles = [srv.submit_async(p, n_new=5) for p in prompts]
        outs = [h.result(timeout=300) for h in handles]
    assert retired.value - before == 3
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(
            out, offline.generate(p[None], n_new=5)[0])


def test_eos_early_retire(net, offline):
    """With eos_id set to a token the greedy decode emits, the request
    retires the tick it appears — shorter result, EOS included."""
    prompt = np.asarray([5, 9, 2, 7], np.int32)
    ref = offline.generate(prompt[None], n_new=10)[0]
    t0 = len(prompt)
    eos = int(ref[t0 + 3])
    first = t0 + int(np.argmax(ref[t0:] == eos))   # first occurrence
    with GenerationServer(net, n_slots=2, max_len=32) as srv:
        out = srv.submit(prompt, n_new=10, eos_id=eos, timeout=300)
    assert out.shape == (first + 1,)
    assert out[-1] == eos
    np.testing.assert_array_equal(out, ref[:first + 1])


def test_slot_reuse_after_retire(net, offline):
    """Sequential requests through one slot: the second admission must
    fully overwrite the first request's cache/state."""
    rng = np.random.default_rng(2)
    with GenerationServer(net, n_slots=1, max_len=32) as srv:
        for _ in range(3):
            p = rng.integers(0, 50, int(rng.integers(3, 8))).astype(
                np.int32)
            out = srv.submit(p, n_new=6, timeout=300)
            np.testing.assert_array_equal(
                out, offline.generate(p[None], n_new=6)[0])


def test_max_length_request_does_not_poison_slot(net, offline):
    """A request ending exactly at max_len parks pos == max_len; the
    slot then idles while the other slot keeps decoding.  The idle
    tick must NOT index the positional table out of bounds (NaN fill)
    and smear NaN K/V into the cache — follow-up requests reusing the
    slot must still match offline decode exactly."""
    rng = np.random.default_rng(7)
    p_full = rng.integers(0, 50, 4).astype(np.int32)     # 4 + 28 = 32
    p_long = rng.integers(0, 50, 8).astype(np.int32)     # 8 + 24 = 32
    with GenerationServer(net, n_slots=2, max_len=32) as srv:
        h1 = srv.submit_async(p_full, n_new=28)
        h2 = srv.submit_async(p_long, n_new=24)
        h1.result(timeout=300)
        h2.result(timeout=300)
        # concurrent follow-ups so BOTH slots (including the one that
        # parked at pos == max_len) get reused
        follow = [rng.integers(0, 50, 5).astype(np.int32)
                  for _ in range(2)]
        hs = [srv.submit_async(p, n_new=8) for p in follow]
        for p, h in zip(follow, hs):
            np.testing.assert_array_equal(
                h.result(timeout=300),
                offline.generate(p[None], n_new=8)[0])


def test_sampling_mode_runs_in_range(net):
    with GenerationServer(net, n_slots=2, max_len=32, temperature=1.0,
                          top_k=5) as srv:
        hs = [srv.submit_async(np.asarray([1, 2, 3], np.int32),
                               n_new=6, seed=s) for s in (0, 1)]
        outs = [h.result(timeout=300) for h in hs]
    for out in outs:
        assert out.shape == (9,)
        assert (out >= 0).all() and (out < 50).all()
        np.testing.assert_array_equal(out[:3], [1, 2, 3])


def test_validation(net):
    with pytest.raises(ValueError, match="top_k"):
        GenerationServer(net, n_slots=1, temperature=1.0, top_k=0)
    with pytest.raises(ValueError, match="top_k"):
        GenerationServer(net, n_slots=1, temperature=1.0, top_k=99)
    with pytest.raises(ValueError, match="temperature"):
        GenerationServer(net, n_slots=1, top_k=5)
    with pytest.raises(ValueError, match="positional"):
        GenerationServer(net, n_slots=1, max_len=64)
    with pytest.raises(ValueError, match="kv_blocks"):
        # 2 blocks of 8 cannot hold one max-length (32-token) request
        GenerationServer(net, n_slots=1, max_len=32, block_size=8,
                         kv_blocks=2)
    with GenerationServer(net, n_slots=1, max_len=32) as srv:
        with pytest.raises(ValueError, match="slot cache length"):
            srv.submit(np.zeros(30, np.int32), n_new=10)
        with pytest.raises(ValueError, match="n_new"):
            srv.submit(np.zeros(4, np.int32), n_new=0)
        with pytest.raises(ValueError, match="1-D"):
            srv.submit(np.zeros((2, 4), np.int32), n_new=2)


@pytest.mark.parametrize("bs,tb", [(8, 1), (8, 8), (16, 1), (16, 8)])
def test_multi_tick_parity_matrix(net, offline, bs, tb):
    """Byte-parity across the paged-KV matrix (block_size x scan
    batching): staggered admission with mixed budgets, an EOS
    early-retire (mid-scan for tb > 1), a cancel, and a shared-prefix
    PAIR whose second request rides the prefix-cache HIT path (>= 1
    full block at either block size) — every greedy output must equal
    offline ``generate()`` exactly, hit and miss paths alike."""
    rng = np.random.default_rng(31 * tb + bs)
    reqs = [(rng.integers(0, 50, t0).astype(np.int32), n_new)
            for t0, n_new in [(3, 12), (5, 7), (4, 10)]]
    shared = rng.integers(0, 50, 17).astype(np.int32)
    ref_shared = offline.generate(shared[None], n_new=6)[0]
    eos_prompt = np.asarray([5, 9, 2, 7], np.int32)
    ref_eos = offline.generate(eos_prompt[None], n_new=10)[0]
    eos = int(ref_eos[4 + 3])                        # retires tick 4
    first = 4 + int(np.argmax(ref_eos[4:] == eos))
    with GenerationServer(net, n_slots=2, max_len=32, tick_batch=tb,
                          block_size=bs, tick_timeout_s=None) as srv:
        h_seed = srv.submit_async(shared, n_new=6)   # seeds the prefix
        handles = []
        for prompt, n_new in reqs:
            handles.append(srv.submit_async(prompt, n_new))
            time.sleep(0.01)                         # stagger joins
        h_eos = srv.submit_async(eos_prompt, n_new=10, eos_id=eos)
        h_cancel = srv.submit_async(np.asarray([1, 2, 3], np.int32),
                                    n_new=20)
        assert h_cancel.cancel() is True
        out_seed = h_seed.result(timeout=300)
        h_hit = srv.submit_async(shared, n_new=6)    # prefix-cache hit
        outs = [h.result(timeout=300) for h in handles]
        out_eos = h_eos.result(timeout=300)
        out_hit = h_hit.result(timeout=300)
        with pytest.raises(CancelledError):
            h_cancel.result(timeout=300)
    np.testing.assert_array_equal(out_seed, ref_shared)
    np.testing.assert_array_equal(out_hit, ref_shared)
    for (prompt, n_new), out in zip(reqs, outs):
        np.testing.assert_array_equal(
            out, offline.generate(prompt[None], n_new=n_new)[0])
    np.testing.assert_array_equal(out_eos, ref_eos[:first + 1])


def test_kernel_route_writes_in_the_kernel_and_decodes_the_same(
        net, offline, monkeypatch):
    """The kernel route (forced; Pallas interpret mode here): the
    pool's rows are whole 128-lane rows, the net's four 8-wide heads
    side by side in one (ISSUE 34), the decode scan carries it
    whole and the paged kernel writes each tick's row itself — three
    requests through two slots, so a slot turns over between scans and
    a free slot rides along, then the first prompt again down the
    prefix-HIT admission (its cached blocks gathered out of the packed
    rows, each head's own) — every greedy token equal to the reference route's
    (= offline decode)."""
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", "pallas")
    routes = telemetry.get_registry().get("paged_route_total")
    count = lambda path: routes.labels(path=path).value
    before = count("pallas_write"), count("pallas"), count("reference")
    hits = telemetry.get_registry().counter("prefix_cache_hits_total")
    rng = np.random.default_rng(26)
    reqs = [(rng.integers(0, 50, t0).astype(np.int32), n_new)
            for t0, n_new in [(9, 7), (3, 12), (6, 5)]]
    with GenerationServer(net, n_slots=2, max_len=32, tick_batch=4,
                          block_size=8, tick_timeout_s=None) as srv:
        # [layers, blocks + scratch, 4 heads / 4 a row, block, lanes]
        assert srv._kc.shape == srv._vc.shape == (2, 9, 1, 8, 128)
        outs = [h.result(timeout=600) for h in
                [srv.submit_async(p, n) for p, n in reqs]]
        h0 = hits.value
        again = srv.submit(reqs[0][0], n_new=reqs[0][1], timeout=600)
        assert hits.value - h0 == 1
    for (prompt, n_new), out in zip(reqs, outs):
        np.testing.assert_array_equal(
            out, offline.generate(prompt[None], n_new=n_new)[0])
    np.testing.assert_array_equal(again, outs[0])
    after = count("pallas_write"), count("pallas"), count("reference")
    # counted once per traced scan program; no scatter-then-read
    assert after[0] > before[0] and after[1:] == before[1:]


def test_cancel_mid_decode_kills_device_slot(net, offline):
    """Cancelling an ACTIVE request releases its slot at the next scan
    boundary AND zeroes its device-side budget (the jitted kill op) —
    the zombie row must stop burning ticks instead of decoding out its
    budget, and the concurrent request still decodes exactly."""
    p_long = np.asarray([1, 2, 3], np.int32)
    p_other = np.asarray([7, 8, 9, 4], np.int32)
    with GenerationServer(net, n_slots=2, max_len=32, tick_batch=4,
                          tick_timeout_s=None) as srv:
        # deterministically throttle the scheduler (~0.25s per loop
        # pass for its first 15 passes): warm scans on this tiny model
        # drain all 28 tokens in a few ms, so an unthrottled run can
        # retire h_long BETWEEN two cancel polls and there would be
        # nothing left to cancel
        with FaultInjector([f"serve_tick_stall@{i}:0.25"
                            for i in range(15)]):
            h_long = srv.submit_async(p_long, n_new=28)
            h_other = srv.submit_async(p_other, n_new=12)
            deadline = time.monotonic() + 60
            while h_long.emitted == 0 and time.monotonic() < deadline:
                time.sleep(0.005)            # admitted and decoding
            assert h_long.cancel() is True
            with pytest.raises(CancelledError):
                h_long.result(timeout=300)
        np.testing.assert_array_equal(
            h_other.result(timeout=300),
            offline.generate(p_other[None], n_new=12)[0])
        # with both retired the pool idles — the cancelled slot's
        # device budget must be 0 (killed), not parked > 0 (zombie)
        deadline = time.monotonic() + 30
        rem = None
        while time.monotonic() < deadline:
            with srv._lock:
                rem = np.asarray(srv._state["remaining"])
            if int(rem.max()) == 0:
                break
            time.sleep(0.01)
        assert int(rem.max()) == 0, rem


def test_per_request_sampling_rides_with_greedy(net, offline):
    """Per-request sampling params as [B] device vectors: a sampled
    request shares the pool with a greedy one (greedy stays
    byte-identical to offline), and — because each slot's PRNG splits
    exactly once per tick it is active — the sampled output is
    reproducible per seed and INVARIANT to the scan batching."""
    pg = np.asarray([4, 5, 6], np.int32)
    ps = np.asarray([1, 2, 3], np.int32)
    outs = {}
    for tb in (1, 8):
        with GenerationServer(net, n_slots=2, max_len=32, tick_batch=tb,
                              tick_timeout_s=None) as srv:
            hg = srv.submit_async(pg, n_new=8)
            hs = srv.submit_async(ps, n_new=8, sampling={
                "temperature": 1.0, "top_k": 5, "seed": 11})
            np.testing.assert_array_equal(
                hg.result(timeout=300),
                offline.generate(pg[None], n_new=8)[0])
            outs[tb] = hs.result(timeout=300)
    for out in outs.values():
        assert out.shape == (11,)
        assert (out >= 0).all() and (out < 50).all()
        np.testing.assert_array_equal(out[:3], ps)
    np.testing.assert_array_equal(outs[1], outs[8])


def test_host_syncs_amortized_by_scan(net):
    """A solo K=8 request in steady state polls the host once per
    scan: 16 new tokens cost exactly 2 device->host syncs (<= 1/K per
    token — the dispatch-overhead win the scan exists for)."""
    reg = telemetry.get_registry()
    syncs = reg.counter("generation_server_host_syncs_total")
    ticks = reg.counter("generation_server_ticks_total")
    p = np.asarray([1, 2, 3], np.int32)
    with GenerationServer(net, n_slots=1, max_len=32, tick_batch=8,
                          tick_timeout_s=None) as srv:
        s0, t0 = syncs.value, ticks.value
        out = srv.submit(p, n_new=16, timeout=300)
    assert out.shape == (19,)
    assert syncs.value - s0 == 2                 # two 8-tick scans
    assert ticks.value - t0 == 16


def test_one_transfer_each_way_a_dispatch(net, offline, guarded_scheduler):
    """ISSUE 32 over scans of 1-4 ticks and four admissions — two
    misses, a prefix hit, a sampled request, a cancel's kill — with the
    scheduler's thread under ``jax.transfer_guard``: what it hands a
    program is ONE packed array through ``_to_device``, what it reads
    back ONE packed array through ``_from_device``; the served tokens
    are unchanged."""
    shared = np.asarray([7, 8, 9, 10, 11, 12, 13, 14, 15], np.int32)
    other = np.asarray([3, 1, 4, 1, 5], np.int32)
    hits = telemetry.get_registry().counter("prefix_cache_hits_total")
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          tick_batch=4, tick_timeout_s=None) as srv:
        before, h0 = guarded_scheduler.counts(), hits.value
        first = srv.submit(shared, n_new=6, timeout=300)
        again = srv.submit_async(shared, n_new=5)       # a prefix hit
        sampled = srv.submit_async(other, n_new=7, sampling={
            "temperature": 1.0, "top_k": 5, "seed": 11})
        outs = [again.result(timeout=300), sampled.result(timeout=300)]
        with FaultInjector([f"serve_tick_stall@{i}:0.05"
                            for i in range(40)]):
            doomed = srv.submit_async(other, n_new=20)
            while doomed.emitted == 0:
                time.sleep(0.005)
            assert doomed.cancel()
            with pytest.raises(CancelledError):
                doomed.result(timeout=300)
            # the kill's dispatch follows the retire
            srv.submit(other, n_new=1, timeout=300)
        d = guarded_scheduler.held(before)
    assert hits.value - h0 == 3     # ``shared`` once, ``other`` twice
    assert d['dispatches_total{program="kill"}'] == 1
    assert d['host_transfers_total{site="scan",dir="h2d"}'] == 0
    np.testing.assert_array_equal(
        first, offline.generate(shared[None], n_new=6)[0])
    np.testing.assert_array_equal(
        outs[0], offline.generate(shared[None], n_new=5)[0])
    assert outs[1].shape == (12,)


# a sampled request's tokens on the tiny net (prompt [1, 2, 3], 8 new,
# temperature 1, top_k 5) as PR 31's tree serves them on this
# installation, by seed: the key made on the host then, in the program now
PARENT_SAMPLED = {
    0: [44, 28, 34, 43, 44, 44, 10, 37],
    11: [15, 5, 23, 20, 28, 20, 37, 16],
    2 ** 31 - 1: [44, 10, 37, 25, 14, 28, 10, 25],
    2 ** 32 - 1: [28, 44, 26, 15, 5, 43, 44, 34],
    2 ** 63 - 1: [28, 44, 26, 15, 5, 43, 44, 34],
}


def test_key_is_derived_in_the_program_to_the_host_s_bits(net):
    """The admit programs make the slot's PRNG key from the packed seed
    word: bit for bit ``jax.random.PRNGKey(seed)`` at the edges of what
    ``submit`` accepts (any signed 64-bit integer), temperature and
    top_p cross as their bits, and a sampled request's tokens are the
    parent's."""
    from deeplearning4j_tpu.parallel import generation_server as gs
    unpack = jax.jit(lambda ops: gs._unpack_admission(ops, 3)[0])
    for seed in (0, 1, 2 ** 31 - 1, 2 ** 32 - 1, 2 ** 63 - 1, -1,
                 -2 ** 63):
        req = gs._Pending(np.asarray([1, 2, 3], np.int32), 8, -1, seed,
                          temperature=0.7, top_k=5, top_p=0.9)
        ops = gs._pack_admission(req, 1, req.prompt)
        assert ops.dtype == np.int32 and ops.shape == (gs._ADMIT_HEAD + 3,)
        slot, t0, n_new, eos, key, temp, tk, tp = unpack(ops)
        np.testing.assert_array_equal(
            np.asarray(key), np.asarray(jax.random.PRNGKey(seed)))
        assert (int(t0), int(slot), int(n_new), int(eos), int(tk)) == (
            3, 1, 8, -1, 5)
        assert (np.float32(temp), np.float32(tp)) == (
            np.float32(0.7), np.float32(0.9))
    ps = np.asarray([1, 2, 3], np.int32)
    with GenerationServer(net, n_slots=2, max_len=32, tick_batch=8,
                          tick_timeout_s=None) as srv:
        for seed, tokens in PARENT_SAMPLED.items():
            out = srv.submit(ps, n_new=8, timeout=300, sampling={
                "temperature": 1.0, "top_k": 5, "seed": seed})
            assert out[3:].tolist() == tokens, seed
        for seed in (2 ** 63, -2 ** 63 - 1):
            with pytest.raises(ValueError, match="seed"):
                srv.submit(ps, n_new=1, sampling={"seed": seed})


@pytest.mark.parametrize("route", ["reference", "pallas"])
def test_paged_blocks_counter_follows_the_route_s_walk(net, monkeypatch,
                                                       route):
    """``generation_server_paged_blocks_total``: ``live`` counts the
    table entries at or before each decoded position; ``dead`` the
    rest of what the read covered — the whole 4-entry table on the
    reference route (a gather), the kernel's chunks on the kernel
    route (here the 32-position table is one chunk: the same count,
    by ``kernels.paged_walk_blocks``).
    ``generation_server_paged_lane_bytes_total``: the bytes of those
    entries' blocks, both layers' K and V, that are heads' numbers
    (``kv``) and that pad a pool row to the lanes (``pad``): none where
    a row is a head, 8 wide; on the kernel route the four heads lie
    side by side in one 128-lane row, 32 lanes of numbers in 128."""
    from deeplearning4j_tpu.kernels import paged_walk_blocks
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", route)
    blocks = telemetry.get_registry().get(
        "generation_server_paged_blocks_total")
    lanes = telemetry.get_registry().get(
        "generation_server_paged_lane_bytes_total")
    count = lambda: (blocks.labels(kind="live").value,
                     blocks.labels(kind="dead").value,
                     lanes.labels(kind="kv").value,
                     lanes.labels(kind="pad").value)
    reqs = [([1, 2, 3], 16), ([4, 5, 6, 7, 8, 9, 10, 11, 12], 9)]
    with GenerationServer(net, n_slots=2, max_len=32, tick_batch=8,
                          block_size=8, tick_timeout_s=None) as srv:
        chunk = srv._walk_chunk
        assert chunk == (4 if route == "reference" else paged_walk_blocks(
            8, srv._kc.shape[2], srv._kc.shape[-1], srv._kc.dtype, 4)[0])
        before = count()
        for h in [srv.submit_async(np.asarray(p, np.int32), n)
                  for p, n in reqs]:
            h.result(timeout=600)
        live, dead, kv, pad = (a - b for a, b in zip(count(), before))
        pool = srv._kc.shape
    # a request's tokens are written at positions t0 .. t0 + n_new - 1
    want = [p // 8 + 1 for t0, n in ((3, 16), (9, 9))
            for p in range(t0, t0 + n)]
    assert live == sum(want)
    assert dead == sum(-(-w // chunk) * chunk - w for w in want)
    # entry by entry: 2 layers x (K, V) x float32; the net's 4 heads of 8
    # numbers a token, in pool rows of ``pool[2]`` heads ``pool[4]`` wide
    assert pool[2:] == ((4, 8, 8) if route == "reference" else (1, 8, 128))
    numbers = held = 0
    for w in want:
        for _ in range(-(-w // chunk) * chunk):
            numbers += 2 * 2 * 4 * 4 * 8 * 8
            held += 2 * 2 * 4 * pool[2] * 8 * pool[4]
    assert (kv, pad) == (numbers, held - numbers)
    assert pad == (0 if route == "reference" else 3 * kv)


def test_useful_share_and_scheduler_host_counters(net):
    """ISSUE 25's counters, where the work happens: tokens emitted ==
    what the handles returned, slot-ticks == sum over dispatches of
    active slots x scan length, scheduler host seconds within the
    run's wall time — and the scheduler's phases as scoped spans."""
    reg, tracer = telemetry.get_registry(), telemetry.get_tracer()
    emitted = reg.counter("generation_server_tokens_emitted_total")
    slot_ticks = reg.counter("generation_server_slot_ticks_total")
    host = reg.counter("generation_server_sched_host_seconds_total",
                       labelnames=("phase",))
    host_s = lambda: sum(host.labels(phase=p).value
                         for p in ("admit", "retire"))
    occ_sum = lambda: reg.snapshot()["histograms"][
        "generation_server_slot_occupancy"]["sum"]

    # solo, K=8: two 8-tick scans of one active slot
    with GenerationServer(net, n_slots=1, max_len=32, tick_batch=8,
                          tick_timeout_s=None) as srv:
        e0, s0 = emitted.value, slot_ticks.value
        srv.submit(np.asarray([1, 2, 3], np.int32), n_new=16, timeout=300)
    assert (emitted.value - e0, slot_ticks.value - s0) == (16, 16)

    # three requests through two slots, single ticks: every dispatch
    # adds its active slots, which the occupancy histogram also sums
    seq0 = max((ev["seq"] for ev in tracer.events()), default=-1)
    prompts = [np.asarray(p, np.int32) for p in ([4, 5], [6, 7, 8], [9])]
    budgets = [5, 3, 7]
    t_wall = time.perf_counter()
    with GenerationServer(net, n_slots=2, max_len=32, tick_batch=1,
                          tick_timeout_s=None) as srv:
        e0, s0, h0, o0 = (emitted.value, slot_ticks.value, host_s(),
                          occ_sum())
        handles = [srv.submit_async(p, n_new=n)
                   for p, n in zip(prompts, budgets)]
        outs = [h.result(timeout=300) for h in handles]
    t_wall = time.perf_counter() - t_wall
    returned = sum(len(o) - len(p) for o, p in zip(outs, prompts))
    assert emitted.value - e0 == returned == sum(budgets)
    assert slot_ticks.value - s0 == round((occ_sum() - o0) * 2) >= returned
    assert 0.0 < host_s() - h0 <= t_wall
    snap = reg.snapshot()
    assert not [k for kind in ("counters", "gauges", "histograms")
                for k in snap[kind] if "request_tokens_per_sec" in k
                or "tokens_per_dispatch" in k]
    spans = [ev for ev in tracer.events() if ev["seq"] > seq0]
    names = {ev["name"] for ev in spans}
    assert {"serve/idle", "serve/admit", "serve/tick",
            "serve/retire"} <= names
    assert sum(ev["args"].get("n", 0) for ev in spans
               if ev["name"] == "serve/admit") == len(prompts)
    assert not [n for n in names if n.startswith("bench/")]


def test_sampling_and_tick_batch_validation(net):
    with pytest.raises(ValueError, match="tick_batch"):
        GenerationServer(net, n_slots=1, max_len=32, tick_batch=0)
    with GenerationServer(net, n_slots=1, max_len=32) as srv:
        p = np.asarray([1, 2, 3], np.int32)
        with pytest.raises(ValueError, match="unknown sampling"):
            srv.submit(p, n_new=2, sampling={"nope": 1})
        with pytest.raises(ValueError, match="temperature"):
            srv.submit(p, n_new=2, sampling={"top_k": 5})
        with pytest.raises(ValueError, match="top_k"):
            srv.submit(p, n_new=2,
                       sampling={"temperature": 1.0, "top_k": 0})
        with pytest.raises(ValueError, match="top_k"):
            srv.submit(p, n_new=2,
                       sampling={"temperature": 1.0, "top_k": 99})


def test_pool_exhaustion_queues_on_blocks(net, offline):
    """BLOCKS, not slots, are the scarce resource: a 4-block pool
    (block_size=8) cannot co-run two 3-block requests even with a
    free slot — the second verifiably waits unadmitted while the
    first decodes, gets the retired blocks, and still decodes exactly;
    afterwards every refcount is drained and the free list is whole."""
    rng = np.random.default_rng(9)
    reqs = [rng.integers(0, 50, 5).astype(np.int32) for _ in range(2)]
    with GenerationServer(net, n_slots=2, max_len=32, block_size=8,
                          kv_blocks=4, prefix_cache=False,
                          tick_timeout_s=None) as srv:
        srv.submit(reqs[0], n_new=2, timeout=300)    # warm the compiles
        # throttle the scheduler (~0.1s/pass) so the waiting state is
        # observable before the first request drains its budget
        with FaultInjector([f"serve_tick_stall@{i}:0.1"
                            for i in range(30)]):
            hs = [srv.submit_async(p, n_new=12) for p in reqs]
            deadline = time.monotonic() + 60
            seen_wait = False
            while time.monotonic() < deadline:
                with srv._lock:
                    n_act, n_pend = len(srv._active), len(srv._pending)
                if n_act == 1 and n_pend == 1 and hs[0].emitted > 0:
                    seen_wait = True     # second queued on blocks, not
                    break                # slots (a slot is free)
                time.sleep(0.005)
            assert seen_wait
            outs = [h.result(timeout=300) for h in hs]
        with srv._lock:
            assert int(srv._block_ref[1:].max(initial=0)) == 0
            assert sorted(srv._blocks_free) == [1, 2, 3, 4]
    for p, out in zip(reqs, outs):
        np.testing.assert_array_equal(
            out, offline.generate(p[None], n_new=12)[0])


def test_prefix_reuse_refcounts_and_release(net, offline):
    """Hash-keyed prefix reuse end to end: the second same-prompt
    admission maps the cached blocks copy-free (prefix_cache_hits /
    kv_blocks_shared count it), retire drains refcounts and parks the
    cached blocks EVICTABLE (resident for the next hit), a cancelled
    request's blocks drain too, and an inline tick-failure recovery
    salvages the pool and reconciles the allocator — outputs
    byte-identical throughout."""
    reg = telemetry.get_registry()
    hits = reg.counter("prefix_cache_hits_total")
    shared_ctr = reg.counter("kv_blocks_shared_total")
    salvaged_blocks = reg.counter("kv_blocks_salvaged_total")
    p = np.arange(1, 14, dtype=np.int32)     # 13 tokens: 3 full blocks
    ref = offline.generate(p[None], n_new=6)[0]
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          tick_timeout_s=None) as srv:
        h0, s0 = hits.value, shared_ctr.value
        np.testing.assert_array_equal(
            srv.submit(p, n_new=6, timeout=300), ref)
        with srv._lock:
            cached = dict(srv._block_hash)
            assert len(cached) == 3              # (13-1)//4
            assert all(srv._block_ref[b] == 0 for b in cached)
            assert set(cached) <= set(srv._evictable)    # resident
        np.testing.assert_array_equal(
            srv.submit(p, n_new=6, timeout=300), ref)
        assert hits.value - h0 == 1
        assert shared_ctr.value - s0 == 3
        # cancel path: an admitted request's blocks drain at the next
        # scan boundary
        with FaultInjector([f"serve_tick_stall@{i}:0.05"
                            for i in range(10)]):
            h = srv.submit_async(np.asarray([7, 8, 9], np.int32),
                                 n_new=24)
            deadline = time.monotonic() + 60
            while h.emitted == 0 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert h.cancel() is True
            with pytest.raises(CancelledError):
                h.result(timeout=300)
        deadline = time.monotonic() + 30
        drained = False
        while time.monotonic() < deadline:
            with srv._lock:
                drained = int(srv._block_ref[1:].max(initial=0)) == 0
            if drained:
                break
            time.sleep(0.01)
        assert drained
        # recovery leg: force the watchdog's recovery path (_recover —
        # same epoch bump + salvage + scheduler restart) while the
        # scheduler sits in a chaos-site stall with the request
        # mid-decode — the slot is salvaged (blocks + table carried
        # over), completes byte-identical, allocator reconciled
        sb0 = salvaged_blocks.value
        with FaultInjector(["serve_tick_stall@0:0.3",
                            "serve_tick_stall@1:1.5"]):
            h = srv.submit_async(p, n_new=19)
            deadline = time.monotonic() + 60
            while h.emitted == 0 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert h.emitted > 0          # mid-decode, budget left
            time.sleep(0.1)               # inside pass 1's 1.5s stall:
                                          # pre-dispatch, so the
                                          # committed pool is NOT
                                          # donated and salvage reads
                                          # it clean
            srv._recover("test-forced recovery")
            out = h.result(timeout=300)
        np.testing.assert_array_equal(
            out, offline.generate(p[None], n_new=19)[0])
        assert salvaged_blocks.value > sb0
        with srv._lock:
            assert int(srv._block_ref[1:].max(initial=0)) == 0
            n_free = len(srv._blocks_free) + len(srv._evictable)
            assert n_free == srv.kv_blocks


@pytest.mark.slow
def test_multi_tick_soak_large_k(net, offline):
    """16 staggered mixed-budget requests (some EOS) through 4 slots
    at tick_batch=16 — the large-K steady state the bench ladder runs,
    all byte-identical to offline decode."""
    rng = np.random.default_rng(5)
    with GenerationServer(net, n_slots=4, max_len=32, tick_batch=16,
                          tick_timeout_s=None) as srv:
        reqs, handles = [], []
        for i in range(16):
            t0 = int(rng.integers(3, 8))
            n_new = int(rng.integers(4, 24 - t0))
            p = rng.integers(0, 50, t0).astype(np.int32)
            reqs.append((p, n_new))
            handles.append(srv.submit_async(p, n_new=n_new))
            if i % 3 == 0:
                time.sleep(0.01)
        for (p, n_new), h in zip(reqs, handles):
            np.testing.assert_array_equal(
                h.result(timeout=300),
                offline.generate(p[None], n_new=n_new)[0])


@pytest.mark.slow
def test_paged_shared_prefix_soak(net, offline):
    """Block-churn soak: 12 requests through 2 slots and a TIGHT
    6-block pool (block_size=4), alternating between two long shared
    prefixes with unique tails — constant allocation, refcount churn,
    prefix-cache hits AND LRU evictions under pressure; every greedy
    output byte-identical to offline decode, allocator whole at the
    end."""
    rng = np.random.default_rng(11)
    prefixes = [rng.integers(0, 50, 9).astype(np.int32)
                for _ in range(2)]
    with GenerationServer(net, n_slots=2, max_len=24, block_size=4,
                          kv_blocks=6, tick_batch=8,
                          tick_timeout_s=None) as srv:
        reqs, handles = [], []
        for i in range(12):
            tail = rng.integers(0, 50, int(rng.integers(1, 4))) \
                .astype(np.int32)
            p = np.concatenate([prefixes[i % 2], tail])
            n_new = int(rng.integers(3, 9))
            reqs.append((p, n_new))
            handles.append(srv.submit_async(p, n_new=n_new))
            if i % 3 == 0:
                time.sleep(0.01)
        for (p, n_new), h in zip(reqs, handles):
            np.testing.assert_array_equal(
                h.result(timeout=300),
                offline.generate(p[None], n_new=n_new)[0])
        with srv._lock:
            assert int(srv._block_ref[1:].max(initial=0)) == 0
            assert (len(srv._blocks_free) + len(srv._evictable)
                    == srv.kv_blocks)


def test_stats_prefix_warmth_and_drain(net, offline):
    """The PR 9 introspection trio on ONE server: stats() is one
    lock-consistent router view (slots, queue, block headroom,
    per-instance prefix hit/miss split), prefix_warmth() is a
    bytes-verified membership probe, and drain() closes admission
    while already-submitted work completes byte-identically with the
    scheduler (healthy(), stats()) still alive — distinct from
    shutdown(drain=True), which also stops the scheduler."""
    p = np.arange(1, 14, dtype=np.int32)     # 3 full blocks @ bs=4
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          tick_batch=1, tick_timeout_s=None) as srv:
        st = srv.stats()
        assert st["healthy"] and not st["draining"]
        assert st["live_slots"] == 0 and st["free_slots"] == 2
        assert st["queue_depth"] == 0
        assert st["free_blocks"] == srv.kv_blocks
        assert st["prefix_hits"] == 0 and st["prefix_misses"] == 0
        assert srv.prefix_warmth(p) == 0
        out = srv.submit(p, n_new=6, timeout=300)
        assert srv.prefix_warmth(p) == 3     # (13-1)//4 full blocks
        assert srv.prefix_warmth(
            np.asarray([9, 9, 9, 9, 9], np.int32)) == 0
        srv.submit(p, n_new=6, timeout=300)
        st = srv.stats()
        assert st["prefix_hits"] == 1 and st["prefix_misses"] == 1
        assert st["cached_blocks"] == 3
        # drain with a request in flight (the hit path — compiled)
        h = srv.submit_async(p, n_new=6)
        srv.drain()
        with pytest.raises(RuntimeError, match="draining"):
            srv.submit(p, n_new=2)
        np.testing.assert_array_equal(
            h.result(timeout=300), offline.generate(p[None],
                                                    n_new=6)[0])
        assert srv.stats()["draining"] is True
        assert srv.healthy()                 # draining is not dead


def test_generate_rejects_out_of_range_top_k(net):
    # ADVICE r5: JAX index clamping silently disabled filtering before
    gen = TransformerGenerator(net)
    prompt = np.asarray([[1, 2, 3]], np.int32)
    with pytest.raises(ValueError, match="top_k"):
        gen.generate(prompt, n_new=2, temperature=1.0, top_k=0)
    with pytest.raises(ValueError, match="top_k"):
        gen.generate(prompt, n_new=2, temperature=1.0, top_k=51)
    out = gen.generate(prompt, n_new=2, temperature=1.0, top_k=50)
    assert out.shape == (1, 5)


# -- the seam between the server and the one generator -----------------
def _tiny_hybrid(**kw):
    from deeplearning4j_tpu.zoo.hybrid_decoder import HybridDecoder
    cfg = dict(vocab_size=50, d_model=32, n_layers=4, d_ff=64, n_heads=4,
               n_kv_heads=2, attn_period=2, attn_offset=1, d_state=8,
               d_conv=4, expand=2, dt_rank=2, seq_len=8,
               compute_dtype=None, seed=3)
    cfg.update(kw)
    return HybridDecoder(**cfg).init_graph()


@pytest.mark.parametrize("kind", ["post_ln", "runs"])
def test_one_generator_one_signature(net, kind):
    """Whatever the stack, ``TransformerGenerator(net)`` is that class
    and answers with the runs' signatures: four values, ``rec`` None
    exactly when the stack keeps no recurrent state -- and a server
    over such a stack carries no array for it."""
    import jax.numpy as jnp
    net = net if kind == "post_ln" else _tiny_hybrid()
    gen = TransformerGenerator(net)
    assert type(gen) is TransformerGenerator
    emb_p, blk_ps, head_p = gen._params()
    runs_p = gen._stack_blocks(blk_ps)
    assert isinstance(runs_p, tuple) and len(runs_p) == len(gen.runs)
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    logits, ks, vs, rec = gen._prefill_rows(emb_p, runs_p, head_p, prompt)
    assert ks.shape == (gen.kv_layers, 1, gen.kv_heads, 4, gen.head_dim)
    pad = ((0, 0), (0, 0), (0, 0), (0, 4), (0, 0))
    out = gen._step(emb_p, runs_p, head_p, jnp.pad(ks, pad),
                    jnp.pad(vs, pad), rec, logits.argmax(-1), 4)
    assert len(out) == 4 and out[0].shape == (1, gen.vocab_size)
    recurrent = kind == "runs"
    assert (rec is not None) == recurrent == (out[3] is not None)
    assert (gen.fresh_rec(2) is not None) == recurrent
    with GenerationServer(net, n_slots=2, max_len=16, block_size=4,
                          prefix_cache=False) as srv:
        assert len(srv._params) == 3
        assert ("rec_h" in srv._state) == ("rec_conv" in srv._state) \
            == recurrent


def test_attention_only_run_stack_refused_by_name_then_served():
    """A stack of runs with NO recurrent run (the shape of a pre-norm
    grouped-query decoder) has no chunked ``sequence()``: the default
    ``prefix_cache=True`` is refused at construction by what the run
    kind cannot do, not served until the first prefix hit dies inside
    a trace.  Without the cache the same prompt twice is served and
    equals offline ``generate()``."""
    net = _tiny_hybrid(attn_period=1, attn_offset=0)
    gen = TransformerGenerator(net)
    assert gen.fresh_rec(1) is None and "AttentionBlockRun" in gen.refuses
    kw = dict(n_slots=2, max_len=32, block_size=4)
    for on, what in [({}, "prefix_cache=True"),
                     ({"prefix_cache": False, "speculative": {"k": 2}},
                      "speculative decode")]:
        with pytest.raises(ValueError, match=f"{what} is not supported for "
                                             "a net with AttentionBlockRun"):
            GenerationServer(net, **kw, **on)
    prompt = np.arange(1, 10, dtype=np.int32)     # two full blocks
    ref = gen.generate(prompt[None], n_new=6)[0]
    with GenerationServer(net, prefix_cache=False, **kw) as srv:
        assert not any(k.startswith("rec_") for k in srv._state)
        for _ in range(2):
            np.testing.assert_array_equal(
                srv.submit(prompt, n_new=6, timeout=300), ref)
        with pytest.raises(ValueError, match="export_prefix is not "
                                             "supported for a net with "
                                             "AttentionBlockRun"):
            srv.export_prefix(prompt)
