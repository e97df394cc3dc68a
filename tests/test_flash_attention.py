"""Pallas flash-attention kernel: forward parity, gradients (custom
VJP), block-size handling.  Runs in interpret mode on CPU; the same
kernel compiles via Mosaic on TPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import flash_attention
from deeplearning4j_tpu.parallel.ring_attention import (
    full_attention_reference)


def _qkv(b=2, h=2, t=64, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
                 for _ in range(3))


def test_flash_matches_reference():
    q, k, v = _qkv()
    out = flash_attention(q, k, v, blk_q=16, blk_k=16)
    ref = full_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


def test_flash_single_block_and_clamping():
    q, k, v = _qkv(t=8)
    out = flash_attention(q, k, v)  # blocks clamp 128 -> 8
    ref = full_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


def test_flash_gradients_match_reference():
    q, k, v = _qkv(t=32, d=8)

    def loss_flash(args):
        return jnp.sum(jnp.square(
            flash_attention(*args, blk_q=8, blk_k=8)))

    def loss_ref(args):
        return jnp.sum(jnp.square(full_attention_reference(*args)))

    gf = jax.grad(loss_flash)((q, k, v))
    gr = jax.grad(loss_ref)((q, k, v))
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4)


def _masked_reference(q, k, v, bias=None, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if bias is not None:
        s = s + bias[:, None, None, :]
    if causal:
        t = q.shape[2]
        m = np.tril(np.ones((t, t), bool))
        s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def test_flash_causal_matches_reference():
    q, k, v = _qkv()
    out = flash_attention(q, k, v, 16, 16, causal=True)
    ref = _masked_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


def test_flash_causal_ragged_blocks():
    """blk_k < blk_q: diagonal blocks have fully-masked rows — the
    phantom-mass guard must keep them exact."""
    q, k, v = _qkv()
    out = flash_attention(q, k, v, 32, 8, causal=True)
    ref = _masked_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


def test_flash_bias_padding_mask():
    q, k, v = _qkv()
    bias = np.zeros((2, 64), np.float32)
    bias[:, 50:] = -1e9
    out = flash_attention(q, k, v, 16, 16, bias=jnp.asarray(bias))
    ref = _masked_reference(q, k, v, bias=jnp.asarray(bias))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


@pytest.mark.parametrize("kw", [{}, {"causal": True}, {"bias": True}])
def test_flash_gradients_masked(kw):
    """Pallas backward kernels (dq + dkdv) vs XLA autodiff reference,
    for plain, causal, and padding-bias attention."""
    q, k, v = _qkv(t=32, d=8)
    bias = None
    if kw.pop("bias", False):
        b = np.zeros((2, 32), np.float32)
        b[:, 25:] = -1e9
        bias = jnp.asarray(b)

    def loss_flash(args):
        return jnp.sum(jnp.square(
            flash_attention(*args, 8, 8, bias=bias, **kw)))

    def loss_ref(args):
        return jnp.sum(jnp.square(
            _masked_reference(*args, bias=bias, **kw)))

    gf = jax.grad(loss_flash)((q, k, v))
    gr = jax.grad(loss_ref)((q, k, v))
    for a, b2 in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                   atol=5e-4)


def test_flash_uniformly_masked_rows_stay_finite():
    """A row whose every key carries the -1e9 bias degenerates to an
    ordinary softmax (softmax is shift-invariant) — the kernel must
    stay NaN/Inf-free and match the reference there, fwd and bwd."""
    q, k, v = _qkv(t=16, d=8)
    bias = jnp.full((2, 16), -1e9, jnp.float32)  # mask EVERYTHING

    out = flash_attention(q, k, v, 8, 8, bias=bias)
    ref = _masked_reference(q, k, v, bias=bias)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)

    def loss(args):
        return jnp.sum(flash_attention(*args, 8, 8, bias=bias))

    for g in jax.grad(loss)((q, k, v)):
        assert np.isfinite(np.asarray(g)).all()


def test_flash_bias_gradient_not_silently_zero():
    """Regression (round-3 review): the custom VJP must propagate a
    REAL bias cotangent — a learned/ALiBi-style bias routed through
    flash must not train with silent zero gradients."""
    q, k, v = _qkv(t=32, d=8)
    bias0 = jnp.asarray(
        np.random.default_rng(5).normal(size=(2, 32)).astype(np.float32))

    def loss_flash(b):
        return jnp.sum(jnp.square(
            flash_attention(q, k, v, 8, 8, bias=b)))

    def loss_ref(b):
        return jnp.sum(jnp.square(_masked_reference(q, k, v, bias=b)))

    gf = jax.grad(loss_flash)(bias0)
    gr = jax.grad(loss_ref)(bias0)
    assert float(jnp.max(jnp.abs(gr))) > 1e-3   # reference is nonzero
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               atol=5e-4)


def test_flash_bias_gradient_with_causal_and_heads():
    """Bias grad with causal masking and per-head bias broadcasting."""
    q, k, v = _qkv(t=32, d=8)
    bias0 = jnp.asarray(
        np.random.default_rng(6).normal(size=(2, 2, 32))
        .astype(np.float32))

    def loss_flash(b):
        return jnp.sum(jnp.square(
            flash_attention(q, k, v, 16, 16, bias=b, causal=True)))

    def loss_ref(b):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        s = s + b[:, :, None, :]
        m = np.tril(np.ones((32, 32), bool))
        s = jnp.where(m[None, None], s, -1e30)
        p = jax.nn.softmax(s, -1)
        return jnp.sum(jnp.square(jnp.einsum("bhqk,bhkd->bhqd", p, v)))

    gf = jax.grad(loss_flash)(bias0)
    gr = jax.grad(loss_ref)(bias0)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               atol=5e-4)


def test_attention_entry_routes_and_fallbacks():
    """attention(): query-dependent bias and short t fall back to the
    XLA path with identical semantics."""
    from deeplearning4j_tpu.kernels import attention
    q, k, v = _qkv(t=16, d=8)
    qbias = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 1, 16, 16)),
        jnp.float32)
    out = attention(q, k, v, bias=qbias)
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d) + qbias
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


def test_flash_rejects_ragged_blocks():
    q, k, v = _qkv(t=48)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, blk_q=32, blk_k=32)


def test_self_attention_layer_flash_flag_parity():
    """SelfAttentionLayer(use_flash=True) must produce the same outputs
    as the einsum path (flash engages only on the unmasked path)."""
    from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers_misc import SelfAttentionLayer
    from deeplearning4j_tpu.nn.conf.layers_recurrent import RnnOutputLayer
    from deeplearning4j_tpu.optimize.updaters import Sgd

    def build(use_flash):
        b = (NeuralNetConfiguration.builder().seed(3)
             .updater(Sgd(learning_rate=0.1)).list()
             .set_input_type(InputType.recurrent(8))
             .layer(SelfAttentionLayer(n_heads=2, head_size=4, n_out=8,
                                       use_flash=use_flash))
             .layer(RnnOutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent")))
        return MultiLayerNetwork(b.build()).init()

    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 16, 8)).astype(np.float32)
    m_ein, m_flash = build(False), build(True)
    np.testing.assert_allclose(np.asarray(m_flash.output(x)),
                               np.asarray(m_ein.output(x)), atol=3e-5)


def test_bthd_layout_matches_bhtd_fwd_and_grad():
    """layout='bthd' reads [b, t, h, d] in place: outputs and all
    gradients must match the transposed bhtd call exactly."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.kernels import flash_attention
    rng = np.random.default_rng(0)
    b, h, t, d = 2, 3, 64, 16
    mk = lambda: jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    bias = jnp.asarray(
        np.where(rng.random((b, t)) < 0.2, -1e9, 0.0), jnp.float32)
    for kw in ({}, {"causal": True}, {"bias": bias},
               {"causal": True, "bias": bias}):
        o_bthd = flash_attention(q, k, v, 16, 16, layout="bthd", **kw)
        o_ref = flash_attention(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
            16, 16, **kw).swapaxes(1, 2)
        np.testing.assert_allclose(np.asarray(o_bthd),
                                   np.asarray(o_ref), atol=2e-5)

        def loss(fn, args, lay):
            return jnp.sum(flash_attention(
                *args, 16, 16, layout=lay, **kw).astype(jnp.float32)
                ** 2)
        g1 = jax.grad(lambda a: loss(None, a, "bthd"))((q, k, v))
        g2 = jax.grad(lambda a: loss(None, a, "bhtd"))(
            tuple(x.swapaxes(1, 2) for x in (q, k, v)))
        for a, bb in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a),
                                       np.asarray(bb.swapaxes(1, 2)),
                                       atol=2e-4)


def test_attention_bthd_routes_and_falls_back():
    import jax.numpy as jnp
    from deeplearning4j_tpu import kernels
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
    kernels.reset_route_log()
    out = kernels.attention(q, q, q, causal=True, layout="bthd")
    assert out.shape == (2, 64, 2, 16)
    assert kernels.route_log() == (("xla", 64, 16),)  # t<512 -> xla
    ref = kernels.attention(q.swapaxes(1, 2), q.swapaxes(1, 2),
                            q.swapaxes(1, 2), causal=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.swapaxes(1, 2)),
                               atol=2e-5)


def test_attention_maps_flash_over_a_declared_mesh():
    """GSPMD cannot partition a Mosaic kernel (the chip's compiler
    refuses the program; interpret mode never notices), so under a
    DECLARED multi-device mesh attention() runs flash per (batch,
    head) shard through shard_map: values and grads equal the plain
    call.  A mesh axis the call cannot map over falls back to XLA,
    counted by the long-t alarm."""
    from jax.sharding import Mesh
    from deeplearning4j_tpu import kernels, telemetry
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 512, 2, 8)), jnp.float32)
               for _ in range(3))
    bias = jnp.asarray(
        np.where(rng.random((2, 512)) < 0.2, -1e9, 0.0), jnp.float32)

    def loss(q, k, v):
        out = kernels.attention(q, k, v, bias=bias, causal=True,
                                layout="bthd")
        return jnp.sum(out * out), out

    (_, want), want_g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                           has_aux=True)(q, k, v)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    kernels.reset_route_log()
    with mesh, kernels.trace_mesh(mesh):
        (_, got), got_g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    assert kernels.route_log() == (("flash", 512, 8),)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-5)

    alarm = telemetry.get_registry().get(
        "flash_fallback_above_threshold_total")
    before = alarm.value
    odd = Mesh(np.array(jax.devices()[:2]), ("sequence",))
    kernels.reset_route_log()
    with kernels.trace_mesh(odd):
        kernels.attention(q, k, v, causal=True, layout="bthd")
    assert kernels.route_log() == (("xla", 512, 8),)
    assert alarm.value == before + 1


# -- paged decode attention (PR 7) -------------------------------------
def _paged_fixture(seed=0, B=3, h=4, dh=8, bs=4, mb=4, nb=9):
    rng = np.random.default_rng(seed)
    kpool = jnp.asarray(rng.normal(size=(nb, h, bs, dh)), jnp.float32)
    vpool = jnp.asarray(rng.normal(size=(nb, h, bs, dh)), jnp.float32)
    tbl = jnp.asarray(rng.integers(1, nb, (B, mb)), jnp.int32)
    pos = jnp.asarray([3, 7, 13], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, h, dh)), jnp.float32)
    return q, kpool, vpool, tbl, pos, 1.0 / dh ** 0.5


def test_paged_reference_matches_stripe_math():
    """The gather-based reference path must be BYTE-identical to the
    stripe decode-step math on the table's contiguous view — the
    parity contract the serving tests build on."""
    from deeplearning4j_tpu.kernels import (paged_decode_attention,
                                            paged_gather)
    from deeplearning4j_tpu.kernels.paged_attention import (
        paged_decode_attention_reference)
    q, kp, vp, tbl, pos, scale = _paged_fixture()
    ref = paged_decode_attention_reference(q, kp, vp, tbl, pos, scale)
    kl, vl = paged_gather(kp, tbl), paged_gather(vp, tbl)
    L = kl.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, None, :],
                   kl).astype(jnp.float32)
    s = s * scale
    valid = (jnp.arange(L)[None, :] <= pos[:, None])[:, None, None, :]
    s = jnp.where(valid, s, -1e9)
    p = jax.nn.softmax(s, -1).astype(vl.dtype)
    stripe = jnp.einsum("bhqk,bhkd->bhqd", p, vl)[:, :, 0]
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(stripe))
    # the public router takes the reference path off-TPU
    out = paged_decode_attention(q, kp, vp, tbl, pos, scale=scale)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_paged_pallas_interpret_matches_reference():
    """The Pallas kernel (interpret mode on CPU, Mosaic on TPU) agrees
    with the reference to float tolerance, including context lengths
    that end mid-block and unused table tails."""
    from deeplearning4j_tpu.kernels.paged_attention import (
        _paged_decode_pallas, paged_decode_attention_reference)
    q, kp, vp, tbl, pos, scale = _paged_fixture()
    ref = paged_decode_attention_reference(q, kp, vp, tbl, pos, scale)
    out = _paged_decode_pallas(q, kp, vp, tbl, pos, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


# -- the decode tick's in-kernel write (PR 26) --------------------------
# slot s owns pool blocks 4s+1 .. 4s+4 (block 0 is the scratch sink);
# each case: per-slot (position, state) with state "live" (writes at
# its table's block), "free" (empty table, writes scratch) or
# "retired" (LIVE table, sent to scratch: retired mid-scan)
_WRITE_CASES = {
    "woff_first": [(4, "live"), (8, "live"), (12, "live")],
    "woff_mid": [(5, "live"), (9, "live"), (2, "live")],
    "woff_last": [(3, "live"), (7, "live"), (15, "live")],
    "write_block_is_first_block": [(0, "live"), (2, "live"), (3, "live")],
    "free_slots_beside_a_live_table": [(0, "free"), (6, "live"),
                                       (0, "free")],
    "retired_slot_keeps_its_table": [(9, "live"), (0, "retired"),
                                     (14, "live")],
}


def _write_case(case, L=3, h=4, dh=8, bs=4, mb=4, dtype=jnp.float32,
                seed=0, width=None, g=1):
    """``width`` > dh: a kernel-route pool, its rows padded with zeros
    to the lane width; ``g`` query heads a K/V head."""
    rng = np.random.default_rng(seed)
    B = len(case)
    nb = B * mb + 1
    shape = (L, nb, h, bs, dh)
    pad = [(0, 0)] * 4 + [(0, (width or dh) - dh)]
    kp = jnp.pad(jnp.asarray(rng.normal(size=shape), dtype), pad)
    vp = jnp.pad(jnp.asarray(rng.normal(size=shape), dtype), pad)
    q, kn, vn = (jnp.asarray(rng.normal(size=(B, heads, dh)), dtype)
                 for heads in (h * g, h, h))
    tbl = np.zeros((B, mb), np.int32)
    pos, wblk, woff = (np.zeros((B,), np.int32) for _ in range(3))
    for s, (p, state) in enumerate(case):
        if state != "free":
            tbl[s] = np.arange(s * mb + 1, s * mb + 1 + mb)
        if state == "live":
            pos[s], wblk[s], woff[s] = p, tbl[s, p // bs], p % bs
    live = np.asarray([state == "live" for _, state in case])
    return (q, kn, vn, kp, vp, *map(jnp.asarray, (tbl, pos, wblk, woff)),
            live)


def _assert_write_read_matches_scatter_then_reference(ops, layer, atol):
    from deeplearning4j_tpu.kernels.paged_attention import (
        _paged_decode_write_pallas, paged_decode_attention_reference)
    from deeplearning4j_tpu.kernels import pad_head_dim
    q, kn, vn, kp, vp, tbl, pos, wblk, woff, live = ops
    dh, width = q.shape[-1], kp.shape[-1]
    scale = 1.0 / dh ** 0.5
    out, ko, vo = _paged_decode_write_pallas(
        q, kn, vn, kp, vp, tbl, pos, wblk, woff, layer, scale)
    kr = kp.at[layer, wblk, :, woff, :].set(pad_head_dim(kn, width))
    vr = vp.at[layer, wblk, :, woff, :].set(pad_head_dim(vn, width))
    ref = paged_decode_attention_reference(
        q, kr[layer, ..., :dh], vr[layer, ..., :dh], tbl, pos, scale)
    # the pool BITWISE off the scratch block, every layer: the layers
    # beside ``layer`` and a retired slot's live blocks untouched
    np.testing.assert_array_equal(np.asarray(ko[:, 1:], np.float32),
                                  np.asarray(kr[:, 1:], np.float32))
    np.testing.assert_array_equal(np.asarray(vo[:, 1:], np.float32),
                                  np.asarray(vr[:, 1:], np.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               atol=atol)


@pytest.mark.parametrize("case", sorted(_WRITE_CASES))
def test_paged_write_kernel_matches_scatter_then_reference(case):
    """The decode tick's kernel (interpret mode here) writes the new
    row and reads through it: the pool equals the XLA scatter's bit for
    bit, the output the reference's on the scattered pool."""
    _assert_write_read_matches_scatter_then_reference(
        _write_case(_WRITE_CASES[case]), layer=1, atol=2e-5)


# the walk's own edges: 24-entry tables of 16-token blocks under four
# K/V heads, which the kernel walks 8 blocks (128 positions) a chunk,
# so a context of 384 spans three chunks
_WALK = dict(L=2, h=4, dh=8, bs=16, mb=24)
_WALK_CASES = {
    "ends_on_a_block_s_last_row": [(63, "live"), (15, "live"),
                                   (271, "live")],
    "ends_on_a_chunk_s_last_block": [(117, "live"), (255, "live"),
                                     (127, "live")],
    "ends_in_a_chunk_s_first_block": [(128, "live"), (259, "live"),
                                      (143, "live")],
    "one_live_block": [(0, "live"), (9, "live"), (15, "live")],
    "full_table": [(383, "live"), (382, "live")],
    "free_and_retired_between_live": [(200, "live"), (0, "free"),
                                      (300, "live"), (0, "retired"),
                                      (130, "live")],
    # block 8 is alone in the second chunk, block 16 in the third
    "write_block_first_and_last_in_its_chunk": [(129, "live"),
                                                (256, "live"),
                                                (140, "live")],
    "every_slot_free": [(0, "free"), (0, "free")],
}


# ONE K/V head (``jamba2-3b``'s): a chunk wants 512 positions, and is
# 24 of the 48 table entries here
_WALK_ONE_HEAD = {**_WALK, "h": 1, "dh": 64, "mb": 48}
_WALK_ONE_HEAD_CASES = {
    "around_the_chunk_s_edge": [(383, "live"), (384, "live"),
                                (399, "live")],
    "full_table_and_one_block": [(767, "live"), (7, "live")],
    "free_and_retired_between_live": [(400, "live"), (0, "free"),
                                      (0, "retired"), (20, "live")],
}


def test_walk_cases_span_chunks():
    from deeplearning4j_tpu.kernels import paged_walk_blocks
    assert paged_walk_blocks(_WALK["bs"], _WALK["h"], _WALK["dh"],
                             jnp.float32, _WALK["mb"])[0] == 8
    assert paged_walk_blocks(16, 1, 128, jnp.bfloat16, 48)[0] == 24


@pytest.mark.parametrize("g", [1, 3], ids=["a_head_a_head", "grouped"])
@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_paged_write_kernel_walks_live_chunks_only(case, g):
    """Tables longer than a chunk: the kernel's own copies, the chunk
    loop's trip count and the patched block's way back, at the edges
    the walk has; grouped, 3 query heads read each K/V head."""
    _assert_write_read_matches_scatter_then_reference(
        _write_case(_WALK_CASES[case], g=g, **_WALK), layer=1, atol=2e-5)


@pytest.mark.parametrize("case", sorted(_WALK_ONE_HEAD_CASES))
def test_paged_write_kernel_grouped_on_one_kv_head(case):
    """Multi-query attention as ``jamba2-3b`` runs it: ONE K/V head, 5
    query heads on it, bfloat16 in a lane-wide pool."""
    _assert_write_read_matches_scatter_then_reference(
        _write_case(_WALK_ONE_HEAD_CASES[case], g=5, dtype=jnp.bfloat16,
                    width=128, **_WALK_ONE_HEAD), layer=0, atol=2e-2)


# (bs, h, width, mb, slots' query heads): the benchmark's two serving
# cells, then the server's defaults (float32 pool, 128-entry tables)
@pytest.mark.parametrize("bs,h,width,dtype,mb,walk", [
    (16, 16, 128, jnp.bfloat16, 32, (8, 3)),
    (128, 1, 128, jnp.bfloat16, 8, (4, 8)),
    (16, 12, 128, jnp.float32, 128, (8, 3)),
    (16, 2, 8, jnp.float32, 3, (3, 8)),
    (16, 64, 256, jnp.float32, 32, (2, 2))],
    ids=["closed_decode", "closed_reasoning", "server_default",
         "table_shorter_than_a_chunk", "vmem_bound"])
def test_paged_walk_blocks_reads_the_walk_from_the_shapes(bs, h, width,
                                                          dtype, mb, walk):
    """(blocks a chunk, buffers): a chunk divides the table (or is all
    of it) and holds 128 positions a K/V head, 512 where one head is
    all there is, as far as the table and the VMEM budget allow; two
    buffers at least, and all of them inside the budget."""
    from deeplearning4j_tpu.kernels import paged_attention as pa
    chunk, buffers = pa.paged_walk_blocks(bs, h, width, dtype, mb)
    assert (chunk, buffers) == walk and mb % chunk == 0
    block = 2 * h * bs * width * jnp.dtype(dtype).itemsize
    assert 2 <= buffers <= 8
    assert buffers * chunk * block <= pa._WALK_VMEM_BYTES
    assert chunk == mb or chunk * bs <= 128 * max(1, 4 // h)


@pytest.mark.parametrize("chunk", [1, 8, 32])
def test_scheduler_counts_the_blocks_the_kernel_s_loop_covers(chunk):
    """``generation_server_paged_blocks_total``: live + dead of a scan
    are the entries the kernel's chunk loop covers, tick by tick, for
    slots that start at made-up positions and stop after made-up
    numbers of ticks; ``chunk`` = the table: the reference's gather."""
    from deeplearning4j_tpu.parallel.generation_server import \
        _paged_blocks_walked
    bs, mb = 16, 32
    pos0 = np.asarray([0, 15, 16, 127, 128, 300, 503])
    ticks = np.asarray([8, 1, 0, 3, 8, 8, 8])
    live = dead = 0
    for p0, n in zip(pos0, ticks):
        for p in range(p0, p0 + n):
            here = sum(j * bs <= p for j in range(mb))
            loops = -(-here // chunk)            # the kernel's trip count
            live, dead = live + here, dead + loops * chunk - here
    assert _paged_blocks_walked(pos0, ticks, bs, chunk) == (live, dead)
    assert _paged_blocks_walked(pos0[:0], ticks[:0], bs, chunk) == (0, 0)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_paged_write_kernel_touches_one_layer_of_the_pool(layer):
    """Two neighbours of one 5-D pool: layer ``l``'s write leaves
    layers ``l - 1`` and ``l + 1`` as they were."""
    _assert_write_read_matches_scatter_then_reference(
        _write_case(_WRITE_CASES["woff_mid"], seed=layer), layer=layer,
        atol=2e-5)


@pytest.mark.parametrize("width", [64, 128], ids=["dh_wide", "lane_wide"])
def test_paged_write_kernel_at_served_widths_bf16(width):
    """16-token blocks of 64-wide heads in bfloat16, the benchmark's
    block, in a pool as wide as the heads and in the kernel route's
    (rows padded to the 128 lanes): the written rows are bf16 bit for
    bit, the padding stays zero."""
    case = [(37, "live"), (16, "live"), (0, "free"), (0, "retired")]
    _assert_write_read_matches_scatter_then_reference(
        _write_case(case, L=2, h=4, dh=64, bs=16, mb=3,
                    dtype=jnp.bfloat16, width=width), layer=1, atol=2e-2)


# -- several heads a pool row (ISSUE 34) ---------------------------------
# (K/V heads, dh, query heads a K/V head) -> heads a row by the rule:
# as many whole heads as fit the 128 lanes and divide the kind's
_PACKED = {
    "two_a_row": (4, 64, 1, 2),
    "two_a_row_grouped": (4, 64, 2, 2),
    "four_a_row": (8, 32, 1, 4),
    "three_a_row_and_padding": (3, 40, 1, 3),
    "one_a_row": (3, 64, 1, 1),
}
_PACKED_SLOTS = [(9, "live"), (0, "free"), (14, "live"), (0, "retired"),
                 (4, "live")]


def _packed(pool, heads):
    from deeplearning4j_tpu.kernels import paged_pool_rows
    return paged_pool_rows(pool, heads, 128)


@pytest.mark.parametrize("case", sorted(_PACKED))
def test_paged_write_kernel_on_a_pool_of_several_heads_a_row(case):
    """``paged_decode_write_attention`` over a pool whose rows hold p
    K/V heads side by side against scatter + the gather reference over
    the heads' own view: the output to 1e-5; the written row in its own
    head's lanes of the right layer and block; the companions' lanes,
    the padding past the last head and every other layer bit for bit; a
    free and a retired slot (``wblk == 0``) writing nothing."""
    from deeplearning4j_tpu.kernels import (paged_decode_write_attention,
                                            paged_head_rows)
    from deeplearning4j_tpu.kernels.paged_attention import (
        paged_decode_attention_reference)
    h, dh, g, p = _PACKED[case]
    q, kn, vn, kp, vp, tbl, pos, wblk, woff, live = _write_case(
        _PACKED_SLOTS, L=3, h=h, dh=dh, g=g, seed=34)
    layer, scale = 1, 1.0 / dh ** 0.5
    out, ko, vo = paged_decode_write_attention(
        q, kn, vn, _packed(kp, h // p), _packed(vp, h // p), tbl, pos, wblk,
        woff, layer, scale)
    assert ko.shape == vo.shape == kp.shape[:2] + (h // p, kp.shape[3], 128)
    kr = kp.at[layer, wblk, :, woff, :].set(kn)
    vr = vp.at[layer, wblk, :, woff, :].set(vn)
    ref = paged_decode_attention_reference(q, kr[layer], vr[layer], tbl, pos,
                                           scale)
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               atol=1e-5)
    for got, want in ((ko, kr), (vo, vr)):
        # off the scratch block: the heads' own view, then the rows as
        # the pool holds them (the padding lanes still zero)
        np.testing.assert_array_equal(
            np.asarray(paged_head_rows(got, h, dh)[:, 1:]),
            np.asarray(want[:, 1:]))
        np.testing.assert_array_equal(
            np.asarray(got[:, 1:]), np.asarray(_packed(want, h // p)[:, 1:]))
    # a written row differs from what lay there, in its own lanes only
    s, j = 0, 1                                   # slot 0, K/V head 1
    lanes = slice((j % p) * dh, (j % p + 1) * dh)
    row = np.asarray(ko[layer, wblk[s], j // p, woff[s]])
    was = np.asarray(_packed(kp, h // p)[layer, wblk[s], j // p, woff[s]])
    np.testing.assert_array_equal(row[lanes], np.asarray(kn[s, j]))
    assert not np.array_equal(row[lanes], was[lanes])


@pytest.mark.parametrize("W", [1, 3], ids=["decode", "verify"])
@pytest.mark.parametrize("case", ["two_a_row", "four_a_row",
                                  "three_a_row_and_padding"])
def test_paged_read_kernels_on_a_pool_of_several_heads_a_row(case, W):
    """The scatter-then-read kernels (the speculative programs' on the
    kernel route) over such a pool: W query positions a slot, p rows a
    position a pool head, each query head its own segment back."""
    from deeplearning4j_tpu.kernels.paged_attention import (
        _paged_verify_pallas, paged_verify_attention_reference)
    h, dh, _, p = _PACKED[case]
    _, kp, vp, tbl, pos, scale = _paged_fixture(seed=5, h=h, dh=dh)
    q = jnp.asarray(np.random.default_rng(6).normal(size=(3, W, h, dh)),
                    jnp.float32)
    ref = paged_verify_attention_reference(q, kp, vp, tbl, pos, scale)
    out = _paged_verify_pallas(q, _packed(kp, h // p), _packed(vp, h // p),
                               tbl, pos, scale, p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("case", sorted(_PACKED))
def test_the_pool_s_two_views_are_each_other_s_way_back(case):
    """Head rows -> pool rows -> head rows is the identity; head
    ``H * p + j`` lies in lanes ``j * dh ..`` of pool head ``H``; what is
    left of a row is zero."""
    from deeplearning4j_tpu.kernels import paged_head_rows, paged_pool_rows
    h, dh, _, p = _PACKED[case]
    rows = jnp.asarray(np.random.default_rng(7).normal(size=(2, 5, h, 4, dh)),
                       jnp.bfloat16)
    pool = paged_pool_rows(rows, h // p, 128)
    assert pool.shape == (2, 5, h // p, 4, 128)
    np.testing.assert_array_equal(
        np.asarray(paged_head_rows(pool, h, dh), np.float32),
        np.asarray(rows, np.float32))
    for head in range(h):
        np.testing.assert_array_equal(
            np.asarray(pool[:, :, head // p, :,
                            (head % p) * dh:(head % p + 1) * dh], np.float32),
            np.asarray(rows[:, :, head], np.float32))
    assert not np.asarray(pool[..., p * dh:], np.float32).any()


@pytest.mark.parametrize("case", sorted(_PACKED))
def test_a_block_leaves_for_the_host_the_same_from_either_pool(case):
    """``_block_to_host``: the host tier's and a prefix handoff's bytes
    are [layers, h, block_size, dh] whatever the pool's rows hold, so a
    block exported by a replica whose pool holds p heads a row imports
    into one that holds a head a row, and back."""
    from types import SimpleNamespace
    from deeplearning4j_tpu.parallel.generation_server import \
        GenerationServer
    h, dh, _, p = _PACKED[case]
    pool = jnp.asarray(np.random.default_rng(8).normal(size=(3, 6, h, 4, dh)),
                       jnp.bfloat16)
    srv = SimpleNamespace(_gen=SimpleNamespace(kv_heads=h))
    plain = GenerationServer._block_to_host(srv, pool, 4, dh)
    assert plain.shape == (3, h, 4, dh)
    np.testing.assert_array_equal(plain, np.asarray(pool[:, 4]))
    for heads in (h // p, h):                 # p heads a row; one, padded
        np.testing.assert_array_equal(
            GenerationServer._block_to_host(srv, _packed(pool, heads), 4, dh),
            plain)


@pytest.mark.parametrize("h,qk,v,route,want", [
    (16, 64, 64, "pallas", 2),        # bert-large-causal.closed-decode
    (1, 128, 128, "pallas", 1),       # jamba2-3b.closed-reasoning
    (4, 192, 128, "pallas", 1),       # mimo-v2-flash, the full kind
    (8, 192, 128, "pallas", 1),       # ... and its window kind
    (4, 24, 16, "pallas", 1),         # unequal widths under the lanes
    (12, 64, 64, "pallas", 2), (6, 128, 128, "pallas", 1),
    (4, 8, 8, "pallas", 4), (3, 40, 40, "pallas", 3), (9, 32, 32, "pallas", 3),
    (16, 64, 64, "reference", 1), (4, 8, 8, "reference", 1)],
    ids=["closed_decode", "closed_reasoning", "long_reasoning_full",
         "long_reasoning_window", "unequal_widths", "gpt2", "zoo_gpt",
         "tiny_gpt", "padding_left", "largest_divisor",
         "off_the_kernel_route", "tiny_off_the_kernel_route"])
def test_heads_a_pool_row_by_the_rule(monkeypatch, h, qk, v, route, want):
    """``paged_heads_a_row`` and the pool shape it gives, from (K/V
    heads, widths, route) alone: the largest divisor of the heads whose
    rows fit the 128 lanes, on the kernel route, keys and values equally
    wide; else a head a row -- ``dh`` wide off the kernel route."""
    from deeplearning4j_tpu import kernels
    from deeplearning4j_tpu.kernels import paged_attention as pa
    monkeypatch.setattr(pa, "_route", lambda: route)
    assert kernels.paged_heads_a_row(h, qk, v) == want
    wide = lambda d: -(-want * d // 128) * 128 if route == "pallas" else d
    assert kernels.paged_pool_shape(h, 16, qk, v) == (
        (h // want, 16, wide(qk)), (h // want, 16, wide(v)))

    class Mesh:
        tp = 2
    assert kernels.paged_heads_a_row(h, qk, v, Mesh) == 1
    assert kernels.paged_pool_shape(h, 16, qk, v, Mesh) == ((h, 16, qk),
                                                            (h, 16, v))


@pytest.mark.parametrize("entry", ["decode", "verify"])
def test_paged_read_kernels_skip_a_lane_wide_pool_s_padding(entry):
    """The scatter-then-read kernels (speculative programs on the
    kernel route) over a pool whose rows are padded to the lane width
    read what they read from the narrow pool."""
    from deeplearning4j_tpu.kernels.paged_attention import (
        _paged_decode_pallas, _paged_verify_pallas)
    q, kp, vp, tbl, pos, scale = _paged_fixture(seed=3)
    pad = [(0, 0)] * 3 + [(0, 128 - kp.shape[-1])]
    if entry == "decode":
        fn = _paged_decode_pallas
    else:
        fn = _paged_verify_pallas
        rng = np.random.default_rng(4)
        q = jnp.asarray(rng.normal(size=(3, 3, 4, 8)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(fn(q, jnp.pad(kp, pad), jnp.pad(vp, pad), tbl, pos,
                      scale)),
        np.asarray(fn(q, kp, vp, tbl, pos, scale)))


def test_paged_write_route_is_counted():
    """``paged_route_total{path="pallas_write"}`` counts the traces of
    the in-kernel-write entry: a server that fell back to
    scatter-then-read shows ``pallas`` instead."""
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.kernels import paged_decode_write_attention

    def count(path):
        return telemetry.get_registry().get(
            "paged_route_total").labels(path=path).value

    before = count("pallas_write"), count("pallas")
    q, kn, vn, kp, vp, tbl, pos, wblk, woff, _ = _write_case(
        _WRITE_CASES["woff_mid"])
    paged_decode_write_attention(q, kn, vn, kp, vp, tbl, pos, wblk,
                                 woff, 0)
    assert (count("pallas_write"), count("pallas")) == (before[0] + 1,
                                                        before[1])


# -- paged multi-query verification (PR 11, speculative decode) --------
def test_paged_verify_reference_unrolls_to_single_query():
    """Each query row of the W-wide verification reference must be
    BYTE-identical to the single-query decode attention at that row's
    position — the speculative parity contract (the reference unrolls
    per row precisely so a W-row einsum cannot regroup reductions)."""
    from deeplearning4j_tpu.kernels import (paged_decode_attention,
                                            paged_verify_attention)
    from deeplearning4j_tpu.kernels.paged_attention import (
        paged_verify_attention_reference)
    rng = np.random.default_rng(1)
    q1, kp, vp, tbl, pos, scale = _paged_fixture(seed=1)
    W = 3
    q = jnp.asarray(rng.normal(size=(3, W, 4, 8)), jnp.float32)
    ref = paged_verify_attention_reference(q, kp, vp, tbl, pos, scale)
    for j in range(W):
        row = paged_decode_attention(q[:, j], kp, vp, tbl, pos + j,
                                     scale=scale)
        np.testing.assert_array_equal(np.asarray(ref[:, j]),
                                      np.asarray(row))
    out = paged_verify_attention(q, kp, vp, tbl, pos, scale=scale)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_paged_verify_pallas_interpret_matches_reference():
    """The multi-query Pallas verification kernel (interpret mode on
    CPU) agrees with the per-row-unrolled reference to float
    tolerance, at chunk positions ending mid-block."""
    from deeplearning4j_tpu.kernels.paged_attention import (
        _paged_verify_pallas, paged_verify_attention_reference)
    rng = np.random.default_rng(2)
    _, kp, vp, tbl, pos, scale = _paged_fixture(seed=2)
    W = 3
    q = jnp.asarray(rng.normal(size=(3, W, 4, 8)), jnp.float32)
    ref = paged_verify_attention_reference(q, kp, vp, tbl, pos, scale)
    out = _paged_verify_pallas(q, kp, vp, tbl, pos, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# flash_prefill: the inference forward over a prompt -- a band, grouped K/V
# ---------------------------------------------------------------------------
def _dense_attention(hq, hkv, window, q, k, v, t0):
    """``AttentionBlockRun._attend_block`` -- the dense route the kernel
    replaces -- a query block of 256 at a time, over the first ``t0``
    queries."""
    from deeplearning4j_tpu.nn.conf.layers_hybrid import AttentionBlockRun
    run = AttentionBlockRun(n_heads=hq, n_kv_heads=hkv, head_dim=128,
                            window=window)
    run.infer_shapes((q.shape[1], 128))
    b, t = q.shape[:2]
    qg = q.reshape(b, t, hkv, hq // hkv, 128)
    block = jax.jit(lambda qb, at: run._attend_block(qb, k, v, at, None))
    outs = [block(qg[:, a:a + 256], jnp.arange(a, min(a + 256, t)))
            for a in range(0, t0, 256)]
    return jnp.concatenate(outs, axis=1)[:, :t0].reshape(b, t0, hq, 128)


@pytest.mark.parametrize("t", [384, 1024, 2048])
@pytest.mark.parametrize("hq,hkv,window", [(48, 8, None), (64, 8, 512)],
                         ids=["full_48_on_8", "window_512_64_on_8"])
def test_flash_prefill_matches_the_dense_route(hq, hkv, window, t):
    """Cell 5's two kinds of attention layer (48 query heads on 8 K/V
    heads, full; 64 on 8 in a 512-wide band), bf16: the kernel that
    visits only the blocks the causal triangle or the band reaches,
    K/V heads grouped in its index maps, against ``_attend_block``
    over every key -- the real positions of a prompt padded past its
    length ``t0``, within bf16's step at the outputs' size."""
    from deeplearning4j_tpu.kernels import flash_prefill
    t0 = t - 37
    ks = jax.random.split(jax.random.PRNGKey(t + hq), 3)
    q = jax.random.normal(ks[0], (1, t, hq, 128), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, t, hkv, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, t, hkv, 128), jnp.bfloat16)
    got = jax.jit(lambda q, k, v: flash_prefill(
        q, k, v, window=window, scale=128 ** -0.5))(q, k, v)
    assert got.shape == q.shape and got.dtype == jnp.bfloat16
    want = _dense_attention(hq, hkv, window, q, k, v, t0)
    np.testing.assert_allclose(np.asarray(got[:, :t0], np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_flash_prefill_band_reaches_across_blocks():
    """A band narrower than a block and not aligned to one (window 100,
    blocks of 128 at t = 384; rows whose band starts in the next block
    meet a tile with none of their keys first), a prompt that is not a
    multiple of 128 (t = 300, padded inside), in float32: the masked
    softmax over every key, exactly up to float32 rounding."""
    from deeplearning4j_tpu.kernels import flash_prefill
    for t, window in ((384, 100), (300, 7), (300, None)):
        rng = np.random.default_rng(t)
        q = jnp.asarray(rng.normal(size=(2, t, 4, 16)), jnp.float32)
        k, v = (jnp.asarray(rng.normal(size=(2, t, 2, 16)), jnp.float32)
                for _ in range(2))
        got = flash_prefill(q, k, v, window=window)
        kk, vv = (jnp.repeat(a, 2, axis=2).swapaxes(1, 2) for a in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q.swapaxes(1, 2), kk) / 4.0
        i, j = np.arange(t)[:, None], np.arange(t)[None]
        seen = (j <= i) & ((j > i - window) if window else True)
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
            jnp.where(seen, s, -1e30), -1), vv).swapaxes(1, 2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def _pallas_calls(jaxpr):
    """(name, grid, block shapes) of every pallas_call in ``jaxpr``, in
    order, nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            gm = eqn.params["grid_mapping"]
            found.append((eqn.params["name"], tuple(gm.grid),
                          tuple(tuple(getattr(n, "block_size", n)
                                      for n in bm.block_shape)
                                for bm in gm.block_mappings)))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    found += _pallas_calls(sub)
    return found


def test_the_training_forward_keeps_its_grid_and_blocks():
    """Cell 1's call ([16, 512, 16, 64] bf16, a [b, tk] bias, not
    causal, through ``attention``): the forward keeps the single-block
    kernel it had -- grid (256, 1), q / k / v / out blocks (1, 512, 64),
    the bias's (1, 8, 512), the lse's (1, 512, 128) -- and the backward
    its two kernels; with no band and as many K/V heads as query heads
    nothing of the new options is traced."""
    from deeplearning4j_tpu import kernels
    b, t, h, d = 16, 512, 16, 64
    qkv = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16)
    bias = jax.ShapeDtypeStruct((b, t), jnp.float32)
    fwd = lambda q, k, v, bias: kernels.attention(q, k, v, bias=bias,
                                                  layout="bthd")
    calls = _pallas_calls(jax.make_jaxpr(fwd)(qkv, qkv, qkv, bias).jaxpr)
    blk, lse = (1, 512, 64), (1, 512, 128)
    assert calls == [("flash_fwd", (256, 1),
                      (blk, blk, blk, (1, 8, 512), blk, lse))]
    loss = lambda *a: jnp.sum(fwd(*a).astype(jnp.float32))
    grad = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        qkv, qkv, qkv, bias).jaxpr
    assert [(n, g) for n, g, _ in _pallas_calls(grad)] == [
        ("flash_fwd", (256, 1)), ("flash_bwd_dkv", (256, 1, 1)),
        ("flash_bwd_dq", (256, 1, 1))]


@pytest.mark.parametrize("t,window,grid", [
    (4096, 512, (64, 8, 2)), (2048, 512, (64, 4, 2)), (4096, None, (48, 8, 8)),
    (384, 100, (64, 3, 2)), (512, 512, (64, 1))])
def test_flash_prefill_grid_covers_only_the_band(t, window, grid):
    """The grid's minor axis is the widest span of key blocks a query
    block's band reaches (two of 512 at window 512, not t / 512), every
    block (1, blk, 128), K/V read at the query's group's head, no lse
    output; a prompt of one block takes the single-block kernel."""
    from deeplearning4j_tpu.kernels import flash_prefill
    hq = grid[0]
    q = jax.ShapeDtypeStruct((1, t, hq, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, t, 8, 128), jnp.bfloat16)
    calls = _pallas_calls(jax.make_jaxpr(
        lambda q, k, v: flash_prefill(q, k, v, window=window))(q, kv, kv).jaxpr)
    blk = (1, 512 if t % 512 == 0 else 128, 128)
    assert calls == [("flash_fwd", grid, (blk,) * 4)]
