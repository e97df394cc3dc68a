"""Mesh-sharded decode tick (ISSUE 17): one replica spanning chips
must be BYTE-IDENTICAL to the single-device server and to offline
``generate()`` — across tp degree, tick fusion depth, paged admission
path (prefix hit vs miss) and speculative on/off.  The parity is by
construction (no contracting dim is ever sharded; ``TpShardCtx.rep``
all-gathers before every feature-axis reduction), and these tests pin
it.  tests/conftest.py forces 8 virtual CPU devices, so tp=2 slices
are always available under CI."""
import numpy as np
import pytest

import jax

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.models.generation import TransformerGenerator
from deeplearning4j_tpu.parallel import GenerationServer
from deeplearning4j_tpu.parallel.mesh import serving_mesh
from deeplearning4j_tpu.parallel.speculative import make_self_draft
from deeplearning4j_tpu.serving import ServingFleet
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


def _route(path):
    return telemetry.get_registry().counter(
        "paged_route_total", labelnames=("path",)).labels(path=path)


def _run_server(net, reqs, **kw):
    with GenerationServer(net, n_slots=2, max_len=32, **kw) as srv:
        handles = [srv.submit_async(p, n) for p, n in reqs]
        outs = [h.result(timeout=300) for h in handles]
        st = srv.stats()
    return outs, st


def test_tp2_parity_miss_hit_and_route(net, offline):
    """The lean core of the matrix: a tp=2 replica (default fused
    tick) serves cold admissions AND a repeated-prompt prefix hit,
    every output byte-identical to offline ``generate()``; the
    attention dispatch takes the ``reference_tp`` route (the Pallas
    kernel is per-device until it is shard_map'd) and the stats
    surface reports the slice."""
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, 50, t0).astype(np.int32), n)
            for t0, n in [(3, 6), (5, 9), (7, 3)]]
    refs = [offline.generate(p[None], n_new=n)[0] for p, n in reqs]
    hits = telemetry.get_registry().counter("prefix_cache_hits_total")
    h0, r0 = hits.value, _route("reference_tp").value
    with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                          devices=jax.devices()[:2]) as srv:
        handles = [srv.submit_async(p, n) for p, n in reqs]
        outs = [h.result(timeout=300) for h in handles]
        # repeat of the longest prompt AFTER its blocks registered:
        # the admission maps the cached prefix (a real hit) and the
        # decode must still be byte-identical
        rep = srv.submit(reqs[1][0], 4, timeout=300)
        st = srv.stats()
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, ref)
    np.testing.assert_array_equal(
        rep, offline.generate(reqs[1][0][None], n_new=4)[0])
    assert hits.value - h0 >= 1         # the repeat rode the cache
    assert _route("reference_tp").value - r0 >= 1
    assert st["tp"] == 2
    assert st["devices"] == [f"{d.platform}:{d.id}"
                             for d in jax.devices()[:2]]


def test_tp2_speculative_parity(net, offline):
    """Speculative decode under tp=2: draft, verify and acceptance all
    run through the sharded programs; a full-depth self-draft accepts
    every proposal and the committed bytes equal offline decode."""
    prompt = np.asarray([2, 7, 1, 8, 2, 8], np.int32)
    ref = offline.generate(prompt[None], n_new=8)[0]
    prop = telemetry.get_registry().counter(
        "generation_server_spec_proposed_total")
    p0 = prop.value
    outs, st = _run_server(
        net, [(prompt, 8)], devices=jax.devices()[:2],
        speculative={"k": 2, "rounds": 2, "draft_layers": 2})
    np.testing.assert_array_equal(outs[0], ref)
    assert prop.value - p0 >= 1
    assert st["spec_acceptance_rate"] == 1.0
    assert st["tp"] == 2


def test_sharded_pool_reports_global_blocks(net):
    """The pool shards its HEAD axis only — the block axis (and the
    host-side allocator) stays global, so the free-KV view the
    autoscaler / placement ranking reads is the whole replica's truth,
    not a per-shard fraction."""
    with GenerationServer(net, n_slots=2, max_len=32,
                          block_size=4) as plain:
        with GenerationServer(net, n_slots=2, max_len=32, block_size=4,
                              devices=jax.devices()[:2]) as sharded:
            assert sharded.stats()["free_blocks"] \
                == plain.stats()["free_blocks"] > 0


def test_geometry_validation_is_pinned(net):
    """Bad mesh geometry fails at CONSTRUCTION with a named reason,
    never as a GSPMD error mid-admission."""
    # tp must divide the head count (the pool's head axis is the shard)
    with pytest.raises(ValueError, match="n_heads=4 must divide"):
        GenerationServer(net, n_slots=2, max_len=32,
                         devices=jax.devices()[:3])
    # the data axis must divide the slot count
    with pytest.raises(ValueError, match="n_slots=3 must divide"):
        GenerationServer(net, n_slots=3, max_len=32,
                         devices=jax.devices()[:4], tp=2)
    # tp must divide the slice
    with pytest.raises(ValueError, match="tp=2 must divide"):
        serving_mesh(jax.devices()[:3], tp=2)
    with pytest.raises(ValueError, match="at least one device"):
        serving_mesh([])
    # an external draft shares the head-sharded pool leaves: its head
    # count must split the same way (the self-draft passes trivially)
    draft = make_self_draft(TransformerGenerator(net))
    draft.check_tp(2)                   # 4 heads / tp=2: fine
    with pytest.raises(ValueError, match="draft n_heads=4"):
        draft.check_tp(3)


def test_fleet_device_slice_validation(net):
    """Per-replica slices must be disjoint (an overlap double-books a
    chip's HBM) and one-per-replica."""
    d = jax.devices()
    with pytest.raises(ValueError, match="slices must be disjoint"):
        ServingFleet(net, n_replicas=2, n_slots=2, max_len=32,
                     devices=[[d[0]], d[:2]])
    with pytest.raises(ValueError, match="devices has 1 slices"):
        ServingFleet(net, n_replicas=2, n_slots=2, max_len=32,
                     devices=[d[:2]])


@pytest.mark.slow
def test_single_device_slice_pins_without_tp(net, offline):
    """A one-device slice still builds a ctx (it PINS the replica to
    that chip — the fleet's mixed-topology case) but keeps tp=1
    semantics: pallas-eligible route, byte parity."""
    prompt = np.asarray([5, 9, 2, 7], np.int32)
    ref = offline.generate(prompt[None], n_new=6)[0]
    rtp0 = _route("reference_tp").value
    outs, st = _run_server(net, [(prompt, 6)],
                           devices=[jax.devices()[1]])
    np.testing.assert_array_equal(outs[0], ref)
    assert st["tp"] == 1
    assert st["devices"] == [f"{jax.devices()[1].platform}:"
                             f"{jax.devices()[1].id}"]
    assert _route("reference_tp").value == rtp0   # no tp forcing


@pytest.mark.slow
@pytest.mark.parametrize("tick_batch", [1, 8])
@pytest.mark.parametrize("spec", [None,
                                  {"k": 2, "rounds": 2,
                                   "draft_layers": 2}])
def test_tp2_matrix(net, offline, tick_batch, spec):
    """The full byte-parity matrix the lean core samples: tp=2 x
    tick_batch in {1, 8} x prefix hit+miss x speculative on/off, each
    cell byte-identical to offline decode AND to a tp=1 server run of
    the same trace."""
    rng = np.random.default_rng(2)
    reqs = [(rng.integers(0, 50, t0).astype(np.int32), n)
            for t0, n in [(3, 6), (6, 8)]]
    kw = dict(tick_batch=tick_batch, block_size=4)
    if spec is not None:
        kw["speculative"] = spec

    def run(**extra):
        with GenerationServer(net, n_slots=2, max_len=32, **kw,
                              **extra) as srv:
            hs = [srv.submit_async(p, n) for p, n in reqs]
            outs = [h.result(timeout=300) for h in hs]
            # sequential repeat: the prefix-HIT admission path
            outs.append(srv.submit(reqs[1][0], 5, timeout=300))
            st = srv.stats()
        return outs, st

    base, _ = run()
    sharded, st = run(devices=jax.devices()[:2])
    assert st["tp"] == 2
    trace = list(reqs) + [(reqs[1][0], 5)]
    for (p, n), one, two in zip(trace, base, sharded):
        ref = offline.generate(p[None], n_new=n)[0]
        np.testing.assert_array_equal(one, ref)
        np.testing.assert_array_equal(two, ref)


@pytest.mark.slow
def test_mixed_fleet_parity_and_gauge(net, offline):
    """ONE fleet mixes a single-chip replica and a tp=2 replica: every
    request decodes byte-identical to offline regardless of placement,
    per-replica stats carry the slice, the scrape exposes
    ``fleet_replica_devices{replica=}``, and live scale-out joins a
    newcomer with its own pinned slice."""
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, 50, t0).astype(np.int32), n)
            for t0, n in [(3, 6), (5, 9), (7, 3)]]
    refs = [offline.generate(p[None], n_new=n)[0] for p, n in reqs]
    with ServingFleet(net, n_replicas=2, n_slots=2, max_len=32,
                      devices=[None, jax.devices()[:2]]) as fleet:
        hs = [fleet.submit_async(p, n) for p, n in reqs]
        for (p, n), h, ref in zip(reqs, hs, refs):
            np.testing.assert_array_equal(h.result(timeout=300), ref)
        st = fleet.stats()
        assert [r["tp"] for r in st["replicas"]] == [1, 2]
        assert st["replicas"][1]["devices"] == [
            f"{d.platform}:{d.id}" for d in jax.devices()[:2]]
        idx = fleet.add_replica(devices=[jax.devices()[2]])
        assert idx == 2
        body = telemetry.get_registry().render_prometheus()
    assert 'fleet_replica_devices{replica="1"} 2.0' in body
    assert 'fleet_replica_devices{replica="2"} 1.0' in body


def test_multi_chip_replica_refuses_the_persistent_compile_cache(
        tmp_path):
    """PR 21, on the chip: a tp=2 decode program LOADED from JAX's
    persistent compilation cache halts the TPU (freshly compiled it
    serves).  JAX decides once per process whether it uses the cache,
    so a multi-chip replica refuses to start beside it — a named error
    at construction, not a halted chip on the second run.  One chip,
    the cache off, or another platform all pass."""
    import types

    from deeplearning4j_tpu.parallel.generation_server import (
        _refuse_cache_loaded_mesh_programs as refuse)
    chip = types.SimpleNamespace(platform="tpu")
    refuse([chip, chip])                      # no cache directory set
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        with pytest.raises(RuntimeError, match="persistent compilation"):
            refuse([chip, chip])
        refuse([chip])                        # one chip loads fine
        refuse(jax.devices()[:2])             # not a TPU
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            refuse([chip, chip])              # the cache switched off
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
