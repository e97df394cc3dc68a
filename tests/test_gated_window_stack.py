"""What ISSUE 36 added to the sparse-window stack, each piece against
plain ``jax.numpy`` at a tiny size on the CPU: the decode kernel's write
into a window's ring over SEVERAL blocks, the arguments that leave the
stack as it was where they are not given, and what the stack refuses."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.generation import TransformerGenerator
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf.layers_hybrid import (AttentionBlockRun,
                                                      rotate_half)
from deeplearning4j_tpu.parallel import GenerationServer
from deeplearning4j_tpu.zoo import SparseWindowDecoder

paged_mod = importlib.import_module(
    "deeplearning4j_tpu.kernels.paged_attention")


@pytest.mark.parametrize("g", [1, 4], ids=["a_head_each", "grouped_x4"])
@pytest.mark.parametrize("positions", [
    [3, 17, 30], [31, 32, 33], [40, 55, 95], [63, 64, 127]],
    ids=["before_the_wrap", "at_the_wrap", "wrapped_once", "wrapped_again"])
def test_the_kernel_writes_a_ring_of_four_blocks_anywhere(positions, g):
    """Window 32 over four 8-position blocks, three slots at the given
    absolute positions (the third retired mid-scan: it writes nothing):
    the kernel in interpret mode, told the table entry the row lands in
    (``pos % 32 // 8``) while it reads ``min(pos + 1, 32)`` rows, against
    XLA's scatter and the gather reference -- the same attention, the
    row in the right block of the right layer and nowhere else."""
    L, B, hkv, dh, bs, mb, window = 2, 3, 2, 16, 8, 4, 32
    hq, nb = hkv * g, 1 + B * mb
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    pad = lambda a: jnp.pad(a, [(0, 0)] * 4 + [(0, 128 - a.shape[-1])])
    kp = pad(jax.random.normal(ks[0], (L, nb, hkv, bs, dh)))
    vp = pad(jax.random.normal(ks[1], (L, nb, hkv, bs, dh)))
    q = jax.random.normal(ks[2], (B, hq, dh))
    kn = jax.random.normal(ks[3], (B, hkv, dh))
    vn = jax.random.normal(ks[4], (B, hkv, dh))
    table = jnp.asarray(1 + np.arange(B * mb).reshape(B, mb), jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    ring = pos % window
    read = jnp.minimum(pos, window - 1)
    wblk = jnp.take_along_axis(table, (ring // bs)[:, None], 1)[:, 0]
    wblk = wblk.at[2].set(0)                  # a slot retired mid-scan
    woff = ring % bs
    att, ko, vo = paged_mod._paged_decode_write_pallas(
        q, kn, vn, kp, vp, table, read, wblk, woff, jnp.int32(1), dh ** -0.5,
        None, ring // bs)
    put = lambda pool, new: pool[1].at[wblk[:2], :, woff[:2], :].set(
        paged_mod.pad_head_dim(new[:2], 128))
    kl, vl = put(kp, kn), put(vp, vn)
    want = paged_mod.paged_decode_attention_reference(
        q, kl[..., :dh], vl[..., :dh], table, read, dh ** -0.5)
    np.testing.assert_allclose(att, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(ko[1], kl)
    np.testing.assert_array_equal(vo[1], vl)
    np.testing.assert_array_equal(ko[0], kp[0])
    if max(positions[:2]) >= window:
        # told nothing, the kernel patches the LAST block it read: the
        # row of a wrapped slot lands in the wrong place
        _, k_last, _ = paged_mod._paged_decode_write_pallas(
            q, kn, vn, kp, vp, table, read, wblk, woff, jnp.int32(1),
            dh ** -0.5)
        assert not np.array_equal(k_last[1], kl)


def _tree_shapes(net):
    return {k: {n: a.shape for n, a in v.items()}
            for k, v in net.params_tree.items()}


def test_arguments_not_given_leave_the_stack_as_it_was():
    """``SparseWindowDecoder``'s new arguments default to what the
    accepted cell's stack is: no gate matrix, no shared expert, one head
    count and one rotary width for both kinds, plain bases -- the same
    leaves of the same shapes, and the same values from the same seed,
    as a stack built with every new argument spelt out as its default."""
    tiny = dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
                window_kv_heads=2, qk_dim=24, v_dim=16, rotary_dim=8,
                window=8, d_ff=64, expert_ff=16, n_experts=8, top_k=2,
                held=(0, 4), seq_len=16, compute_dtype=None)
    plain = MultiLayerNetwork(SparseWindowDecoder(**tiny).conf()).init()
    spelt = MultiLayerNetwork(SparseWindowDecoder(
        **tiny, window_heads=None, window_rotary_dim=None, rope_scaling=None,
        window_rope_scaling=None, gate=False, shared_ff=None,
        routed_scale=None).conf()).init()
    shapes = _tree_shapes(plain)
    assert shapes == _tree_shapes(spelt)
    leaves = set().union(*shapes.values())
    assert not leaves & {"Wg", "Ws_gate", "Ws_up", "Ws_down"}
    for a, b in zip(jax.tree_util.tree_leaves(plain.params_tree),
                    jax.tree_util.tree_leaves(spelt.params_tree)):
        np.testing.assert_array_equal(a, b)
    run = plain.layers[2]
    assert (run.gate, run.shared_ff, run.routed_scale, run.rope_scaling) \
        == (False, None, None, None)
    # and given, each adds its own leaves and nothing else
    gated = MultiLayerNetwork(SparseWindowDecoder(
        **tiny, window_heads=8, gate=True, shared_ff=24,
        routed_scale=2.5).conf()).init()
    more = _tree_shapes(gated)
    assert more["layer_1"]["Wg"] == (1, 32, 4)            # full: 4 heads
    assert more["layer_2"]["Wg"] == (4, 32, 8)            # window: 8
    assert more["layer_2"]["Wq"] == (4, 32, 8 * 24)
    assert more["layer_2"]["Ws_down"] == (4, 24, 32)
    assert "Ws_gate" not in more["layer_1"]               # the dense layer


def test_a_plain_base_is_the_rotation_it_was():
    """``rotate_half`` without ``scaling`` turns lane pairs by ``pos *
    theta^(-i / half)`` and scales nothing."""
    x = jnp.ones((5, 2, 16))
    y = rotate_half(x, jnp.arange(5), 8, 1e4)
    ang = np.arange(5)[:, None] * 1e4 ** (-np.arange(4) / 4.0)
    np.testing.assert_allclose(y[:, 0, :4], np.cos(ang) - np.sin(ang),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])
    with pytest.raises(ValueError, match="only 'yarn'"):
        rotate_half(x, jnp.arange(5), 8, 1e4, {"rope_type": "linear",
                                               "factor": 2.0})


GATED = dict(vocab_size=64, d_model=32, layer_pattern=(0, 1, 1, 0),
             routed_layers=(0, 1, 1, 1), n_heads=4, window_heads=8,
             n_kv_heads=2, window_kv_heads=2, qk_dim=16, v_dim=16,
             rotary_dim=8, window_rotary_dim=16, value_scale=None, window=16,
             window_sink=False, gate=True, d_ff=64, expert_ff=16,
             shared_ff=16, routed_scale=2.5, n_experts=8, top_k=2,
             seq_len=64, compute_dtype=None)


@pytest.mark.parametrize("kw, what", [
    ({"prefix_cache": True}, "prefix reuse"),
    ({"host_tier_blocks": 4}, "host tier"),
    ({"tp": 2}, "tp > 1"),
])
def test_the_gated_stack_refuses_what_a_stack_of_runs_refuses(kw, what):
    """No new refusal: the string names what a gated stack with rings
    over several blocks still cannot do."""
    net = MultiLayerNetwork(SparseWindowDecoder(**GATED).conf()).init()
    assert "several blocks" in AttentionBlockRun.REFUSES
    with pytest.raises(ValueError, match="AttentionBlockRun layers"):
        GenerationServer(net, n_slots=2, max_len=64, block_size=4,
                         **{"prefix_cache": False, **kw})


def test_a_sink_still_keeps_a_ring_to_one_block_on_the_kernel_route(
        monkeypatch):
    """The kernel's write into a ring of several blocks is built and
    measured without a sink; with one the refusal that
    ``tests/benchmark_suite`` pins stands, and without one the same
    geometry is served."""
    monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", "pallas")
    monkeypatch.setenv("DL4J_TPU_EXPERT_KERNEL", "pallas")
    with_sink = MultiLayerNetwork(SparseWindowDecoder(
        **{**GATED, "window_sink": True}).conf()).init()
    with pytest.raises(ValueError, match="a window fits one block"):
        GenerationServer(with_sink, n_slots=2, max_len=64, block_size=4,
                         prefix_cache=False)
    net = MultiLayerNetwork(SparseWindowDecoder(**GATED).conf()).init()
    prompt = np.arange(21, dtype=np.int32) % 64
    with GenerationServer(net, n_slots=2, max_len=64, block_size=4,
                          tick_batch=2, prefix_cache=False) as srv:
        assert srv._state["win_k"].shape == (2, 2 * 4 + 1, 1, 4, 128)
        out = srv.submit_async(prompt, n_new=30).result(timeout=600)
    np.testing.assert_array_equal(
        out, TransformerGenerator(net).generate(prompt[None], 30)[0])
