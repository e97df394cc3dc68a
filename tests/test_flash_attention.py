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
