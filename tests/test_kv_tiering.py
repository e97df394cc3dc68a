"""Tiered HBM->host KV block cache (ISSUE 14): LRU-evicted prefix
blocks SPILL their bytes to a host-RAM tier instead of dying, a later
same-prefix admission restores them with one batched H2D — and every
spill->fetch->re-spill round trip must be BYTE-STABLE (the restored
decode equals the offline decode exactly).  The tier's own LRU is
capacity-bounded and evicts true-LRU; a hash-collision lookup must
degrade to a miss via the raw-token-bytes verification (PR 7's rule
applied to host-tier entries)."""
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.models.generation import TransformerGenerator
from deeplearning4j_tpu.parallel import GenerationServer, HostKVTier
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


def test_host_tier_lru_collision_and_capacity():
    """Pure host-side tier semantics, no servers or compiles: verified
    get/peek, true-LRU capacity eviction (get touches, peek does
    not), and the collision rule — same hash, different token bytes
    is a MISS, never another prompt's KV."""
    with pytest.raises(ValueError, match="capacity"):
        HostKVTier(0)
    tier = HostKVTier(2)
    k1, v1 = np.full((2, 4), 1.0), np.full((2, 4), -1.0)
    k2, v2 = np.full((2, 4), 2.0), np.full((2, 4), -2.0)
    tier.put(11, b"tok-a", k1, v1)
    tier.put(22, b"tok-b", k2, v2)
    # round trip is byte-stable
    got = tier.get(11, b"tok-a")
    np.testing.assert_array_equal(got[0], k1)
    np.testing.assert_array_equal(got[1], v1)
    # collision: right hash, wrong bytes -> miss; entry survives
    assert tier.get(11, b"tok-X") is None
    assert tier.peek(11, b"tok-a") is not None
    # the get() above touched 11, so 22 is now LRU: inserting a third
    # entry at capacity 2 must evict 22, not 11
    tier.put(33, b"tok-c", k1, v1)
    assert len(tier) == 2
    assert tier.get(22, b"tok-b") is None          # true-LRU evicted
    assert tier.peek(11, b"tok-a") is not None
    assert tier.peek(33, b"tok-c") is not None
    # peek does NOT touch: after peeking 11, inserting a fourth entry
    # still evicts 11 (peek left it in LRU position... 11 was MRU from
    # the put-order? order now: 11 (touched), 33 (inserted) -> LRU=11)
    tier.put(44, b"tok-d", k2, v2)
    assert tier.peek(11, b"tok-a") is None
    assert tier.peek(33, b"tok-c") is not None
    assert tier.stats()["blocks"] == 2
    assert tier.discard(33) is True and len(tier) == 1


def test_spill_fetch_respill_byte_stable(net, offline):
    """Server-level round trips through a pool too small for two
    working sets: A decodes cold, B's admission EVICTS A's cached
    blocks (spill), A's re-admission FETCHES them back (one batched
    H2D) and must decode byte-identical — then the cycle repeats
    (B evicts A again -> re-spill -> re-fetch), proving the spilled
    bytes are stable across arbitrarily many round trips.  The
    allocator is whole at the end."""
    reg = telemetry.get_registry()
    fetches = reg.counter("kv_tier_fetches_total")
    pa = np.asarray([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9], np.int32)
    pb = np.asarray([2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4, 5, 9], np.int32)
    ref_a = offline.generate(pa[None], n_new=12)[0]
    ref_b = offline.generate(pb[None], n_new=12)[0]
    f0 = fetches.value
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          kv_blocks=8, host_tier_blocks=8,
                          tick_batch=1, tick_timeout_s=None) as srv:
        # 25-token working sets (7 blocks) through an 8-block pool:
        # each admission evicts most of the other prompt's cache
        for cycle in range(3):
            np.testing.assert_array_equal(
                srv.submit(pa, n_new=12, timeout=300), ref_a)
            np.testing.assert_array_equal(
                srv.submit(pb, n_new=12, timeout=300), ref_b)
        st = srv.stats()
        assert st["tier_spills"] >= 2          # A spilled, re-spilled
        assert st["tier_fetches"] >= 1         # and fetched back
        assert st["tier_hits"] >= 1
        assert st["host_tier_blocks"] >= 1
        # gauge split (ISSUE 14): the stats view carries both halves,
        # summing back to the admission headroom
        assert (st["free_list_blocks"] + st["evictable_blocks"]
                == st["free_blocks"])
        with srv._lock:
            assert int(srv._block_ref[1:].max(initial=0)) == 0
            assert (len(srv._blocks_free) + len(srv._evictable)
                    == srv.kv_blocks)
    assert fetches.value - f0 >= 1


def test_tier_collision_degrades_to_miss(net, offline):
    """A host-tier entry whose chain hash matches the prompt but
    whose RAW TOKEN BYTES do not (a 64-bit hash collision, forced) is
    a MISS: the admission prefills cold and the output is still
    byte-identical — corrupted/foreign KV can never map in."""
    p = np.arange(1, 14, dtype=np.int32)
    ref = offline.generate(p[None], n_new=6)[0]
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          host_tier_blocks=8, tick_batch=1,
                          tick_timeout_s=None) as srv:
        hashes = srv._chain_hashes(p)
        assert len(hashes) == 3
        nl, _, h, bs, dh = srv._kc.shape
        junk = np.full((nl, h, bs, dh), 7.0, np.float32)
        # plant colliding entries: right chain hashes, WRONG bytes
        for hsh, _tok in hashes:
            srv._tier.put(hsh, b"not-these-tokens", junk, junk)
        out = srv.submit(p, n_new=6, timeout=300)
        np.testing.assert_array_equal(out, ref)
        st = srv.stats()
        assert st["tier_fetches"] == 0 and st["tier_hits"] == 0
        assert st["prefix_misses"] >= 1


def test_export_import_handoff_parity(net, offline):
    """The disagg handoff primitive pair on bare servers: a
    prefill-only request registers the prompt's full blocks,
    ``export_prefix`` serializes them, ``import_blocks`` lands them on
    a SECOND server whose admission restores them (tier fetch) and
    decodes byte-identical to offline ``generate()`` — and a second
    same-prefix admission there hits the now-device-resident blocks
    copy-free (no further fetches)."""
    reg = telemetry.get_registry()
    handoff = reg.counter("kv_handoff_blocks_total")
    p = np.arange(2, 19, dtype=np.int32)     # 17 tokens: 4 full @bs=4
    ref = offline.generate(p[None], n_new=6)[0]
    h0 = handoff.value
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          tick_batch=1, tick_timeout_s=None) as src:
        hp = src.prefill_async(p)
        np.testing.assert_array_equal(hp.result(timeout=300), p)
        assert hp.ttft is None and hp.emitted == 0
        payload = src.export_prefix(p)
        assert len(payload) == 4             # (17-1)//4 full blocks
        # the slot and its blocks were released at prefill-retire
        st = src.stats()
        assert st["live_slots"] == 0 and st["cached_blocks"] == 4
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          tick_batch=1, tick_timeout_s=None) as dst:
        assert dst.import_blocks(payload) == 4
        assert dst.prefix_warmth(p) == 4     # tier warmth counts
        np.testing.assert_array_equal(
            dst.submit(p, n_new=6, timeout=300), ref)
        st = dst.stats()
        assert st["tier_fetches"] == 4 and st["tier_hits"] == 1
        np.testing.assert_array_equal(
            dst.submit(p, n_new=6, timeout=300), ref)
        st = dst.stats()
        assert st["tier_fetches"] == 4       # second hit was copy-free
        assert st["prefix_hits"] == 2
        # importing again is a no-op: every block is device-resident
        assert dst.import_blocks(payload) == 0
    assert handoff.value - h0 == 4


def test_spill_and_fetch_through_a_pool_of_several_heads_a_row(
        net, offline, monkeypatch):
    """The kernel route (forced; Pallas interpret mode here) holds the
    tiny net's four 8-wide heads in ONE 128-lane pool row: the spill
    reads a block out of it as [layers, h, block_size, dh], the
    restore's fill lays the bytes back side by side, and two working
    sets through a pool that holds one decode equal to offline."""
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", "pallas")
    pa = np.asarray([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9], np.int32)
    pb = np.asarray([2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4, 5, 9], np.int32)
    ref_a = offline.generate(pa[None], n_new=12)[0]
    ref_b = offline.generate(pb[None], n_new=12)[0]
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          kv_blocks=8, host_tier_blocks=8,
                          tick_batch=1, tick_timeout_s=None) as srv:
        assert srv._kc.shape == srv._vc.shape == (2, 9, 1, 4, 128)
        for _ in range(2):
            np.testing.assert_array_equal(
                srv.submit(pa, n_new=12, timeout=600), ref_a)
            np.testing.assert_array_equal(
                srv.submit(pb, n_new=12, timeout=600), ref_b)
        st = srv.stats()
        assert st["tier_spills"] >= 2 and st["tier_fetches"] >= 1
        # what the tier holds is a head a row, dh wide
        _, k, v = next(iter(srv._tier._entries.values()))
        assert k.shape == v.shape == (2, 4, 4, 8)


@pytest.mark.parametrize("src_route,dst_route", [("pallas", "reference"),
                                                 ("reference", "pallas")])
def test_a_prefix_hands_off_between_pools_of_different_rows(
        net, offline, monkeypatch, src_route, dst_route):
    """A block exported by a replica whose pool holds four heads a row
    (the kernel route) imports into one that holds a head a row (the
    reference route), and back: the payload is [layers, h, block_size,
    dh] either way and the decode on the importer equals offline."""
    p = np.arange(2, 19, dtype=np.int32)     # 17 tokens: 4 full @bs=4
    ref = offline.generate(p[None], n_new=6)[0]
    kw = dict(n_slots=2, max_len=32, block_size=4, tick_batch=1,
              tick_timeout_s=None)
    shape = {"pallas": (2, 17, 1, 4, 128), "reference": (2, 17, 4, 4, 8)}
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", src_route)
    with GenerationServer(net, **kw) as src:
        assert src._kc.shape == shape[src_route]
        np.testing.assert_array_equal(
            src.prefill_async(p).result(timeout=600), p)
        payload = src.export_prefix(p)
    assert len(payload) == 4
    assert all(k.shape == v.shape == (2, 4, 4, 8) for _, _, k, v in payload)
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", dst_route)
    with GenerationServer(net, **kw) as dst:
        assert dst._kc.shape == shape[dst_route]
        assert dst.import_blocks(payload) == 4
        np.testing.assert_array_equal(
            dst.submit(p, n_new=6, timeout=600), ref)
        assert dst.stats()["tier_fetches"] == 4


def test_host_tier_validation(net):
    with pytest.raises(ValueError, match="host_tier_blocks"):
        GenerationServer(net, n_slots=1, max_len=32,
                         host_tier_blocks=-1)
    with pytest.raises(ValueError, match="prefix_cache"):
        GenerationServer(net, n_slots=1, max_len=32,
                         prefix_cache=False, host_tier_blocks=4)
    with GenerationServer(net, n_slots=1, max_len=32,
                          prefix_cache=False) as srv:
        with pytest.raises(ValueError, match="prefill_async"):
            srv.prefill_async(np.asarray([1, 2, 3], np.int32))


def test_spec_prefill_only_claims_no_draft_blocks(net):
    """A speculative server's prefill-ONLY admission claims no draft
    table and runs no draft prefill — the request never decodes, so
    draft KV would be pure waste (a speculative prefill replica would
    otherwise pin ~2x blocks per staged request)."""
    p = np.arange(1, 14, dtype=np.int32)
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          tick_timeout_s=None,
                          speculative={"k": 2, "rounds": 1,
                                       "draft_layers": 2}) as srv:
        h = srv.prefill_async(p)
        np.testing.assert_array_equal(h.result(timeout=300), p)
        with srv._lock:
            assert int(srv._block_ref[1:].max(initial=0)) == 0
            assert len(srv._evictable) == 3      # target blocks ONLY
            assert (len(srv._blocks_free) + len(srv._evictable)
                    == srv.kv_blocks)
        assert len(srv.export_prefix(p)) == 3


@pytest.mark.slow
def test_tier_churn_soak(net, offline):
    """Many distinct prefixes through a tight pool + small tier:
    constant spill/fetch/tier-LRU churn, every output byte-identical,
    allocator whole at the end."""
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, 50, 13).astype(np.int32)
               for _ in range(4)]
    refs = [offline.generate(p[None], n_new=12)[0] for p in prompts]
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          kv_blocks=8, host_tier_blocks=4,
                          tick_batch=1, tick_timeout_s=None) as srv:
        for i in range(16):
            j = i % len(prompts)
            np.testing.assert_array_equal(
                srv.submit(prompts[j], n_new=12, timeout=300), refs[j])
        with srv._lock:
            assert int(srv._block_ref[1:].max(initial=0)) == 0
            assert (len(srv._blocks_free) + len(srv._evictable)
                    == srv.kv_blocks)
        assert len(srv._tier) <= 4           # capacity bound held
