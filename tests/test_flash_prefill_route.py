"""Which attention a prompt's prefill takes: ``AttentionBlockRun.
prefill_route`` -- the flash forward kernel (``kernels.flash_prefill``)
or every key scored in ``jax.numpy`` -- from the prompt's length and the
run's own fields, the same answer in ``sequence()`` and in the server's
count of it (``generation_server_prefill_attention_total``)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.models.generation import TransformerGenerator
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf.layers_hybrid import AttentionBlockRun
from deeplearning4j_tpu.parallel import GenerationServer
from deeplearning4j_tpu.zoo import HybridDecoder, SparseWindowDecoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cell_runs(config: str, zoo):
    """The attention runs of a benchmark configuration's stack (its conf
    alone: nothing is initialised) and the longest bucket its cell's
    prompts fill (the next power of two over the traffic's cap)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        conf = zoo(**json.load(f)["ctor"]).conf()
    cell = next(n for n in os.listdir(os.path.join(ROOT, "benchmark",
                                                   "workloads"))
                if n.startswith(config + "."))
    with open(os.path.join(ROOT, "benchmark", "workloads", cell)) as f:
        cap = json.load(f)["traffic"]["prompt_len"]["cap"]
    return ([l for l in conf.layers if isinstance(l, AttentionBlockRun)],
            1 << (cap - 1).bit_length())


@pytest.mark.parametrize("config,zoo,want", [
    ("laguna-xs.2", SparseWindowDecoder, "kernel"),
    ("jamba2-3b", HybridDecoder, "dense"),
    ("mimo-v2-flash", SparseWindowDecoder, "dense")],
    ids=["cell5_kernel", "cell3_prompts_of_one_block", "cell4_sink_and_192"])
def test_the_route_follows_the_prompt_and_the_run_s_fields(
        monkeypatch, config, zoo, want):
    """On the kernel route (as the paged pool's: the TPU), cell 5's full
    and window runs (48 / 64 query heads on 8 K/V heads of 128, no sink)
    take the kernel at every bucket past one query block, up to the
    longest its prompts fill; cell 3's runs (20 on 1 of 128) are
    eligible but its prompts end at 256, one query block; cell 4's have
    a sink (window) or keys 192 wide beside values 128 (full).  A prompt
    of one query block, or the reference route, is dense anywhere."""
    runs, longest = _cell_runs(config, zoo)
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", "pallas")
    for t in (512, 1024, 2048, 4096):
        if t <= longest:
            assert {r.prefill_route(t) for r in runs} == {want}, t
    assert {r.prefill_route(256) for r in runs} == {"dense"}
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", "reference")
    assert {r.prefill_route(longest) for r in runs} == {"dense"}


@pytest.mark.parametrize("kw,route", [
    ({}, "kernel"), ({"sink": True}, "dense"), ({"qk_dim": 192}, "dense"),
    ({"head_dim": 64}, "dense"), ({"window": 100}, "kernel")])
def test_each_field_the_kernel_cannot_serve_keeps_the_dense_route(
        monkeypatch, kw, route):
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", "pallas")
    run = AttentionBlockRun(**{"n_heads": 8, "n_kv_heads": 2,
                               "head_dim": 128, **kw})
    run.infer_shapes((512, 512))
    assert run.prefill_route(257) == route
    assert run.prefill_route(256) == "dense"


@pytest.mark.parametrize("window", [None, 100], ids=["full", "window_100"])
def test_sequence_on_the_kernel_route_equals_the_dense_route(
        monkeypatch, window):
    """One gated, rotary run of 8 query heads on 2 K/V heads of 128 over
    a padded prompt of 384 (real 300): the output rows before ``t0``,
    the full run's K/V rows and the window run's ring as after token
    ``t0`` agree between the two routes (the kernel in interpret mode,
    float32)."""
    run = AttentionBlockRun(n_heads=8, n_kv_heads=2, head_dim=128,
                            rotary_dim=64, gate=True, window=window)
    run.infer_shapes((384, 256))
    p, _ = run.init(jax.random.PRNGKey(0))
    p = jax.tree_util.tree_map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 384, 256))
    got = {}
    for route in ("reference", "pallas"):
        monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", route)
        got[route] = jax.jit(lambda p, x: run.sequence(
            p, x, jnp.int32(300)))(p, x)
    assert run.prefill_route(384) == "kernel"
    (yd, rows_d), (yk, rows_k) = got["reference"], got["pallas"]
    np.testing.assert_allclose(yk[:, :300], yd[:, :300], rtol=1e-4,
                               atol=1e-4)
    for key in ("k", "v"):
        np.testing.assert_allclose(rows_k[key], rows_d[key], rtol=1e-4,
                                   atol=1e-4)


GATED = dict(vocab_size=64, d_model=32, layer_pattern=(0, 1, 1, 0),
             routed_layers=(0, 1, 1, 1), n_heads=4, window_heads=8,
             n_kv_heads=2, window_kv_heads=2, qk_dim=16, v_dim=16,
             rotary_dim=8, window_rotary_dim=16, value_scale=None,
             window=16, window_sink=False, gate=True, d_ff=64,
             expert_ff=16, shared_ff=16, routed_scale=2.5, n_experts=8,
             top_k=2, seq_len=512, compute_dtype=None)


def _routes():
    snap = telemetry.get_registry().snapshot()["counters"]
    return {r: snap.get("generation_server_prefill_attention_total"
                        f'{{route="{r}"}}', 0.0) for r in ("kernel", "dense")}


@pytest.mark.parametrize("route", ["dense", "kernel"])
def test_an_admission_counts_positions_x_layers_on_the_route_taken(
        monkeypatch, route):
    """A gated window stack of 4 attention layers, a prompt of 300 in
    the bucket of 512: the counter of the route ``prefill_route`` gives
    grows by 512 x 4, the other by 0 -- both series exist, so a share
    of them reads 0 where the kernel is never taken.  On the kernel
    route (forced at these 16-wide heads, so the admit program runs
    ``flash_prefill`` in interpret mode) the server serves offline
    decode's tokens."""
    if route == "kernel":
        monkeypatch.setattr(
            AttentionBlockRun, "prefill_route",
            lambda self, t, shard=None: "kernel" if t > 256 else "dense")
    net = MultiLayerNetwork(SparseWindowDecoder(**GATED).conf()).init()
    prompt = np.arange(300, dtype=np.int32) * 7 % 64
    before = _routes()
    with GenerationServer(net, n_slots=2, max_len=512, block_size=64,
                          tick_batch=2, prefix_cache=False) as srv:
        out = srv.submit_async(prompt, n_new=4).result(timeout=600)
    after = _routes()
    other = "dense" if route == "kernel" else "kernel"
    assert after[route] - before[route] == 512 * 4
    assert after[other] - before[other] == 0
    np.testing.assert_array_equal(
        out, TransformerGenerator(net).generate(prompt[None], 4)[0])
