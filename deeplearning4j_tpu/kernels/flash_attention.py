"""Flash attention as Pallas TPU kernels — forward AND backward.

Forward: a fused streaming-softmax kernel — one grid cell per
(batch*head, q-block), K/V streamed through VMEM in blocks with the
running (max, denominator, accumulator) recurrence, so the [t, t] score
matrix never materializes in HBM (the reason XLA's unfused
attention becomes HBM-bound at long sequence lengths).  Supports a
causal mask (upper-triangular blocks are skipped entirely — ~2x fewer
MXU flops at long t) and an additive key-position bias (the BERT
padding-mask form, [b, tk] broadcast over heads and query positions).

Backward: TWO Pallas kernels (the standard flash-attention backward):
``dkdv`` iterates q-blocks per k-block, ``dq`` iterates k-blocks per
q-block; both recompute the probability tile from the saved per-row
log-sum-exp, so the backward is O(t) memory as well — nothing [t, t]
ever reaches HBM.  ``delta = rowsum(dO * O)`` is precomputed in XLA
(one cheap fused reduction).

Mosaic layout discipline (the r5 rewrite — worth 2-4x in-kernel):
per-row softmax stats (running max / denominator / saved LSE / delta)
are kept LANE-REPLICATED as [blk_q, 128] f32 tiles, never as 1D
[blk_q] vectors.  A 1D row-stat vector lives across the LANE dim, so
broadcasting it back over a [blk_q, blk_k] score tile is a
lane->sublane relayout (a slow Mosaic shuffle) on every K/V step;
the replicated form makes every broadcast a cheap lane-tile
(``jnp.tile(stat, (1, blk_k // 128))``).  The same rule shapes the HBM
residuals: LSE and delta ride as [bh, t, 128] f32 so the backward
kernels read them in their compute layout.  Grid dims are annotated
with ``dimension_semantics`` ("parallel" majors, "arbitrary" minor
accumulation axis) so Mosaic pipelines block DMA behind compute, and
sequences that fit one K/V block (t <= blk_k) take a single-step
kernel with no streaming state at all.

The kernels run identically under ``interpret=True`` (CPU tests) and
compiled (TPU); ``flash_attention`` picks interpret mode automatically
off-TPU so one code path serves both.  Keep q/k/v in bf16 inside the
kernel: an f32 upcast before the dot_generals runs the MXU at 1/8 rate
and makes the kernel 4x SLOWER than XLA.

Parity target: the fused-attention role of the reference's cuDNN helper
seam (``deeplearning4j-cuda`` ``CudnnConvolutionHelper`` analogue for
attention — SURVEY.md §2.1 "Pallas only where XLA is weak").
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

log = logging.getLogger("deeplearning4j_tpu.kernels")
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu import telemetry

_NEG = -1e30   # finite "-inf": keeps the streaming softmax NaN-free
_POS = 1e30    # lse sentinel for fully-masked rows (=> p == 0 in bwd)
_LANES = 128   # TPU lane width: stat tiles are [blk_q, _LANES] f32


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dimsem(*sem):
    return pltpu.CompilerParams(dimension_semantics=sem)


def _causal_tile(j, ki, blk_q, blk_k):
    """Bool [blk_q, blk_k]: col <= row for global positions."""
    rows = j * blk_q + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    cols = ki * blk_k + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    return cols <= rows


def _lane_bcast(stat, width):
    """[blk_q, 128] lane-replicated stat -> broadcastable to
    [blk_q, width].  Aligned widths tile whole 128-lane registers (a
    lane copy); the non-aligned path (interpret mode / d=64) slices,
    which is correct because every lane holds the same value."""
    if width % _LANES == 0:
        return jnp.tile(stat, (1, width // _LANES))
    return stat[:, :1] if width > _LANES else stat[:, :width]


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, n_k: int, scale: float, causal: bool,
                has_bias: bool):
    """Grid (bh, n_q, n_k): the KV dim is the MINOR grid axis, so each
    K/V block copy double-buffers behind the previous block's compute;
    the running softmax state lives in VMEM scratch across KV steps,
    lane-replicated [blk_q, 128] (see module docstring)."""
    if has_bias:
        q_ref, k_ref, v_ref, b_ref = refs[:4]
        o_ref, lse_ref, m_ref, l_ref, acc_ref = refs[4:]
    else:
        q_ref, k_ref, v_ref = refs[:3]
        o_ref, lse_ref, m_ref, l_ref, acc_ref = refs[3:]
        b_ref = None
    j, ki = pl.program_id(1), pl.program_id(2)
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]
    d = q_ref.shape[-1]

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute():
        # Matmuls keep the INPUT dtype (bf16 = full-rate MXU) and
        # accumulate in f32 via preferred_element_type; only the
        # softmax math runs in f32.
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [blk_q, blk_k]
        if has_bias:
            s = s + b_ref[0, :1, :]          # [1, blk_k] sublane splat
        if causal:
            s = jnp.where(_causal_tile(j, ki, blk_q, blk_k), s, _NEG)
        m_prev, l_prev = m_ref[:], l_ref[:]          # [blk_q, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lane_bcast(m_new, blk_k))
        if has_bias:
            # where-guard: for a row fully padded so far s == m_new ==
            # _NEG and exp(0) would contribute phantom mass.  Causal
            # alone can't hit this (ki=0 always gives every row its
            # diagonal mass) — the guard is bias-only.
            p = jnp.where(s > 0.5 * _NEG, p, 0.0)
        corr = jnp.exp(m_prev - m_new)               # [blk_q, 128]
        m_ref[:] = m_new
        l_ref[:] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * _lane_bcast(corr, d) + pv

    if causal:
        # Blocks entirely above the diagonal contribute nothing — skip
        # their matmuls (the source of the ~2x causal speedup).
        pl.when(ki * blk_k <= j * blk_q + blk_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_ref[:]
        empty = l == 0.0          # fully-masked rows -> zero output
        l_safe = jnp.where(empty, 1.0, l)
        o_ref[0] = (acc_ref[:]
                    / _lane_bcast(l_safe, d)).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(empty, _POS, m_ref[:] + jnp.log(l_safe))


def _fwd_kernel_single(*refs, scale: float, causal: bool,
                       has_bias: bool):
    """One K/V block covers the whole row (t <= blk_k): plain softmax,
    no streaming state, no scratch — grid (bh, n_q)."""
    if has_bias:
        q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        b_ref = None
    j = pl.program_id(1)
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]
    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if has_bias:
        s = s + b_ref[0, :1, :]
    if causal:
        s = jnp.where(_causal_tile(j, 0, blk_q, blk_k), s, _NEG)
    m = jnp.max(s, axis=1, keepdims=True)            # [blk_q, 1]
    p = jnp.exp(s - m)
    if has_bias:
        p = jnp.where(s > 0.5 * _NEG, p, 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)            # [blk_q, 1]
    empty = l == 0.0
    l_safe = jnp.where(empty, 1.0, l)
    o_ref[0] = lax.dot_general(
        (p / l_safe).astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)
    lse = jnp.where(empty, _POS, m + jnp.log(l_safe))
    lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


# Names in a profile: an instruction is named by the LAST scope of its
# op_name, and a transformation (jvp, transpose, vmap) wraps the FIRST
# scope opened under it.  The scope around each pallas_call takes that
# wrapping -- ``jvp(flash_attention)/flash_fwd/pallas_call`` -- so the
# kernel's own ``name=`` stays whole and the kernels are ``%flash_fwd.N``,
# ``%flash_bwd_dkv.N`` and ``%flash_bwd_dq.N`` under any of them
# (bare, they would be ``%jvp_flash_fwd_.N`` under ``grad``).  The
# benchmark's readers match these names: tests/test_chip_compile.py.
@jax.named_scope("flash_attention")
def _flash_fwd(q, k, v, bias, blk_q: int, blk_k: int, causal: bool,
               scale: float, bthd: "Static[bool]" = False):
    if bthd:
        # [b, t, h, d] viewed as [b, t, h*d] (a free bitcast): blocks
        # stay (1, blk, d) — Mosaic-legal since d % 128 == 0 — and the
        # third block index SELECTS the head's d-chunk, so the kernel
        # reads the projection layout in place with no transpose.
        b, t, h, d = q.shape
        bh = b * h
        q = q.reshape(b, t, h * d)
        k = k.reshape(b, t, h * d)
        v = v.reshape(b, t, h * d)
        dshape = (b, t, h * d)
        qspec = lambda f: pl.BlockSpec(
            (1, blk_q, d),
            lambda *g: (f(*g)[0] // h, f(*g)[1], f(*g)[0] % h))
        kspec = lambda f: pl.BlockSpec(
            (1, blk_k, d),
            lambda *g: (f(*g)[0] // h, f(*g)[1], f(*g)[0] % h))
    else:
        bh, t, d = q.shape
        h = None
        dshape = (bh, t, d)
        qspec = lambda f: pl.BlockSpec(
            (1, blk_q, d), lambda *g: f(*g) + (0,))
        kspec = lambda f: pl.BlockSpec(
            (1, blk_k, d), lambda *g: f(*g) + (0,))
    n_q = pl.cdiv(t, blk_q)
    n_k = pl.cdiv(t, blk_k)
    has_bias = bias is not None
    # f(*grid) -> (bh_index, block_index) for q/k/v/o data operands
    if n_k == 1:
        q_ix = lambda i, j: (i, j)
        k_ix = lambda i, j: (i, 0)
        in_specs = [qspec(q_ix), kspec(k_ix), kspec(k_ix)]
        inputs = [q, k, v]
        if has_bias:
            in_specs.append(
                pl.BlockSpec((1, 8, blk_k), lambda i, j: (i, 0, 0)))
            inputs.append(bias)
        out, lse = pl.pallas_call(
            functools.partial(_fwd_kernel_single, scale=scale,
                              causal=causal, has_bias=has_bias),
            grid=(bh, n_q),
            in_specs=in_specs,
            out_specs=[
                qspec(q_ix),
                pl.BlockSpec((1, blk_q, _LANES), lambda i, j: (i, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(dshape, q.dtype),
                jax.ShapeDtypeStruct((bh, t, _LANES), jnp.float32),
            ],
            compiler_params=_dimsem("parallel", "parallel"),
            interpret=_interpret(),
            name="flash_fwd",
        )(*inputs)
        return out, lse
    q_ix = lambda i, j, ki: (i, j)
    k_ix = lambda i, j, ki: (i, ki)
    in_specs = [qspec(q_ix), kspec(k_ix), kspec(k_ix)]
    inputs = [q, k, v]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 8, blk_k), lambda i, j, ki: (i, 0, ki)))
        inputs.append(bias)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, n_k=n_k, scale=scale,
                          causal=causal, has_bias=has_bias),
        grid=(bh, n_q, n_k),
        in_specs=in_specs,
        out_specs=[
            qspec(q_ix),
            pl.BlockSpec((1, blk_q, _LANES), lambda i, j, ki: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(dshape, q.dtype),
            jax.ShapeDtypeStruct((bh, t, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((blk_q, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((blk_q, d), jnp.float32),       # output accumulator
        ],
        compiler_params=_dimsem("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="flash_fwd",
    )(*inputs)
    return out, lse


# ---------------------------------------------------------------------------
# Backward — two Pallas kernels, O(t) memory
# ---------------------------------------------------------------------------
def _recompute_p(q_ref, k_ref, b_ref, lse, j, ki, scale, causal,
                 has_bias):
    """Probability tile from the saved [blk_q, 128] LSE (shared by both
    bwd kernels).  Masked/empty entries underflow exp() to exactly 0."""
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]
    s = lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if has_bias:
        s = s + b_ref[0, :1, :]
    if causal:
        s = jnp.where(_causal_tile(j, ki, blk_q, blk_k), s, _NEG)
    return s, jnp.exp(s - _lane_bcast(lse, blk_k))


def _bwd_dkdv_kernel(*refs, n_q: int, scale: float, causal: bool,
                     has_bias: bool):
    """Grid (bh, n_k, n_q): per k-block, stream q-blocks, accumulate
    dK/dV (and, with bias, dBias = sum_q dS_unscaled) in VMEM scratch."""
    if has_bias:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, b_ref,
         dk_ref, dv_ref, db_ref, dk_acc, dv_acc, db_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        b_ref = db_ref = db_acc = None
    ki, qi = pl.program_id(1), pl.program_id(2)
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        if has_bias:
            db_acc[:] = jnp.zeros_like(db_acc)

    def _compute():
        do = do_ref[0]
        lse = lse_ref[0]                     # [blk_q, 128]
        delta = dl_ref[0]                    # [blk_q, 128]
        _, p = _recompute_p(q_ref, k_ref, b_ref, lse, qi, ki, scale,
                            causal, has_bias)
        pb = p.astype(do.dtype)
        dv_acc[:] += lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # p^T @ dO
        dp = lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # dO @ V^T
        ds_f = p * (dp - _lane_bcast(delta, blk_k))   # dS wrt (s+bias)
        if has_bias:
            # The bias cotangent rides back through _broadcast8's vjp
            # (a sum over the 8-replicated sublanes) — divide by 8 so
            # that sum reconstructs sum_q(dS) exactly.
            db_acc[:] += jnp.broadcast_to(
                jnp.sum(ds_f, axis=0, keepdims=True) / 8.0, db_acc.shape)
        ds = (ds_f * scale).astype(do.dtype)
        dk_acc[:] += lax.dot_general(
            ds, q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # dS^T @ Q

    if causal:
        pl.when(qi * blk_q + blk_q - 1 >= ki * blk_k)(_compute)
    else:
        _compute()

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
        if has_bias:
            db_ref[0] = db_acc[:]


def _bwd_dq_kernel(*refs, n_k: int, scale: float, causal: bool,
                   has_bias: bool):
    """Grid (bh, n_q, n_k): per q-block, stream k-blocks, accumulate dQ."""
    if has_bias:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, b_ref,
         dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
         dq_ref, dq_acc) = refs
        b_ref = None
    j, ki = pl.program_id(1), pl.program_id(2)
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute():
        do = do_ref[0]
        lse = lse_ref[0]
        delta = dl_ref[0]
        _, p = _recompute_p(q_ref, k_ref, b_ref, lse, j, ki, scale,
                            causal, has_bias)
        dp = lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - _lane_bcast(delta, blk_k))
              * scale).astype(do.dtype)
        dq_acc[:] += lax.dot_general(
            ds, k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # dS @ K

    if causal:
        pl.when(ki * blk_k <= j * blk_q + blk_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _broadcast8(x, t):
    """[bh, t] f32 -> [bh, 8, t] (Mosaic sublane-aligned input layout)."""
    return jnp.broadcast_to(x.astype(jnp.float32)[:, None, :],
                            (x.shape[0], 8, t))


@jax.named_scope("flash_attention")
def _flash_bwd(q, k, v, bias, out, lse, do, blk_q, blk_k, causal,
               scale, bthd: bool = False):
    if bthd:
        b, t, h, d = q.shape
        bh = b * h
        # out/do arrive as the kernel's [b, t, h*d] view; per-head
        # delta needs the 4D view, in [bh, t] order (b-major, matching
        # the flat grid index decomposition i -> (i // h, i % h))
        out4 = out.reshape(b, t, h, d)
        do4 = do.reshape(b, t, h, d)
        delta = jnp.sum(
            do4.astype(jnp.float32) * out4.astype(jnp.float32), -1)
        delta = delta.transpose(0, 2, 1).reshape(bh, t)
        rs = lambda a: a.reshape(b, t, h * d)
        q, k, v, out, do = rs(q), rs(k), rs(v), rs(out), rs(do)
        dshape = (b, t, h * d)
        qspec = lambda f: pl.BlockSpec(
            (1, blk_q, d),
            lambda *g: (f(*g)[0] // h, f(*g)[1], f(*g)[0] % h))
        kspec = lambda f: pl.BlockSpec(
            (1, blk_k, d),
            lambda *g: (f(*g)[0] // h, f(*g)[1], f(*g)[0] % h))
    else:
        bh, t, d = q.shape
        h = None
        delta = jnp.sum(
            do.astype(jnp.float32) * out.astype(jnp.float32), -1)
        dshape = (bh, t, d)
        qspec = lambda f: pl.BlockSpec(
            (1, blk_q, d), lambda *g: f(*g) + (0,))
        kspec = lambda f: pl.BlockSpec(
            (1, blk_k, d), lambda *g: f(*g) + (0,))
    n_q = pl.cdiv(t, blk_q)
    n_k = pl.cdiv(t, blk_k)
    has_bias = bias is not None
    dl = jnp.broadcast_to(delta[..., None], (bh, t, _LANES))
    stspec = lambda f: pl.BlockSpec((1, blk_q, _LANES), f)

    # --- dK/dV: grid minor axis = q blocks --------------------------------
    in_specs = [
        qspec(lambda i, ki, qi: (i, qi)),                      # q
        kspec(lambda i, ki, qi: (i, ki)),                      # k
        kspec(lambda i, ki, qi: (i, ki)),                      # v
        qspec(lambda i, ki, qi: (i, qi)),                      # do
        stspec(lambda i, ki, qi: (i, qi, 0)),                  # lse
        stspec(lambda i, ki, qi: (i, qi, 0)),                  # delta
    ]
    inputs = [q, k, v, do, lse, dl]
    out_specs = [kspec(lambda i, ki, qi: (i, ki)),
                 kspec(lambda i, ki, qi: (i, ki))]
    out_shape = [jax.ShapeDtypeStruct(dshape, k.dtype),
                 jax.ShapeDtypeStruct(dshape, v.dtype)]
    scratch = [pltpu.VMEM((blk_k, d), jnp.float32),
               pltpu.VMEM((blk_k, d), jnp.float32)]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 8, blk_k), lambda i, ki, qi: (i, 0, ki)))
        inputs.append(bias)
        out_specs.append(
            pl.BlockSpec((1, 8, blk_k), lambda i, ki, qi: (i, 0, ki)))
        out_shape.append(
            jax.ShapeDtypeStruct((bh, 8, t), jnp.float32))
        scratch.append(pltpu.VMEM((8, blk_k), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, n_q=n_q, scale=scale,
                          causal=causal, has_bias=has_bias),
        grid=(bh, n_k, n_q),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_dimsem("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(*inputs)
    dk, dv = outs[0], outs[1]
    dbias8 = outs[2] if has_bias else None

    # --- dQ: grid minor axis = k blocks -----------------------------------
    in_specs = [
        qspec(lambda i, j, ki: (i, j)),
        kspec(lambda i, j, ki: (i, ki)),
        kspec(lambda i, j, ki: (i, ki)),
        qspec(lambda i, j, ki: (i, j)),
        stspec(lambda i, j, ki: (i, j, 0)),
        stspec(lambda i, j, ki: (i, j, 0)),
    ]
    inputs = [q, k, v, do, lse, dl]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 8, blk_k), lambda i, j, ki: (i, 0, ki)))
        inputs.append(bias)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_k=n_k, scale=scale,
                          causal=causal, has_bias=has_bias),
        grid=(bh, n_q, n_k),
        in_specs=in_specs,
        out_specs=qspec(lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct(dshape, q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        compiler_params=_dimsem("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(*inputs)
    return dq, dk, dv, dbias8


# ---------------------------------------------------------------------------
# custom_vjp plumbing
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, bias, blk_q, blk_k, causal, scale, bthd=False):
    out, _ = _flash_fwd(q, k, v, bias, blk_q, blk_k, causal, scale,
                        bthd)
    return out


def _flash_vjp_fwd(q, k, v, bias, blk_q, blk_k, causal, scale,
                   bthd=False):
    out, lse = _flash_fwd(q, k, v, bias, blk_q, blk_k, causal, scale,
                          bthd)
    # Keep the residual compact ([bh, t] — lane 0 of the replicated
    # tile); the backward re-broadcasts to the kernel's [bh, t, 128]
    # layout in XLA, trading one cheap materialization per bwd call
    # for 128x less residual memory held across the forward pass.
    return out, (q, k, v, bias, out, lse[:, :, 0])


def _flash_vjp_bwd(blk_q, blk_k, causal, scale, bthd, res, do):
    q, k, v, bias, out, lse_small = res
    lse = jnp.broadcast_to(lse_small[:, :, None],
                           (*lse_small.shape, _LANES))
    dq, dk, dv, dbias8 = _flash_bwd(q, k, v, bias, out, lse, do, blk_q,
                                    blk_k, causal, scale, bthd)
    if bthd:
        # cotangents must match the 4D primals (the kernels emit the
        # [b, t, h*d] view)
        dq, dk, dv = (a.reshape(q.shape) for a in (dq, dk, dv))
    # dbias8 flows back through _fold_bias's broadcasts (jax sums the
    # 8-replicated sublanes and any head/batch broadcast dims).
    return dq, dk, dv, dbias8


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
def _fold_bias(bias, b, h, t):
    """Accept [b, tk] / [b, h, tk] / [b, 1, 1, tk] (BERT's additive
    padding mask) -> [b*h, 8, tk] f32, or None."""
    if bias is None:
        return None
    bias = jnp.asarray(bias, jnp.float32)
    if bias.ndim == 4:
        if bias.shape[2] != 1:
            raise ValueError(
                "flash bias must be constant over query positions "
                f"(got shape {bias.shape}); use attention() for the "
                "general fallback")
        bias = bias[:, :, 0, :]          # [b, h|1, tk]
    elif bias.ndim == 2:
        if bias.shape[0] != b:
            raise ValueError(
                f"2-D flash bias must be [batch, t_k] (got "
                f"{tuple(bias.shape)} for batch {b}); a [t_q, t_k] "
                "mask is query-dependent — pass causal=True for the "
                "triangular case or use attention()'s XLA fallback")
        bias = bias[:, None, :]          # [b, 1, tk]
    bias = jnp.broadcast_to(bias, (b, h, t)).reshape(b * h, t)
    return _broadcast8(bias, t)


def flash_attention(q, k, v, blk_q: int = 512, blk_k: int = 512, *,
                    bias=None, causal: bool = False,
                    scale: Optional[float] = None,
                    layout: str = "bhtd"):
    """Fused attention: softmax(QK^T*scale + bias)V.

    ``layout="bhtd"`` (default) takes [b, h, t, d].  ``layout="bthd"``
    takes [b, t, h, d] — the natural output of the qkv projection
    split — and the kernels read/write that layout IN PLACE via block
    index maps, so no [b,h,t,d] transpose ever materializes (measured
    ~22 ms/step of transpose churn on zoo.Gpt fwd+bwd without it).

    ``bias`` is an additive key-position mask ([b, tk], [b, h, tk] or
    [b, 1, 1, tk] — finite values only, use -1e9 for padding).
    ``causal=True`` applies the autoregressive mask and skips
    fully-masked blocks.  Block sizes clamp to the sequence length; t
    must divide by the clamped blocks.  Differentiable (custom VJP with
    Pallas backward kernels — O(t) memory both directions)."""
    if layout == "bthd":
        b, t, h, d = q.shape
    else:
        b, h, t, d = q.shape
    blk_q = min(blk_q, t)
    blk_k = min(blk_k, t)
    if t % blk_k or t % blk_q:
        raise ValueError(
            f"sequence length {t} must be divisible by block sizes "
            f"({blk_q}, {blk_k})")
    if blk_k % _LANES and not _interpret():
        # Mosaic layout constraint: the [blk_q, 128] lane-replicated
        # stats broadcast over score tiles by whole-register lane
        # tiling, and the (1, 8, blk_k) bias block needs a lane-aligned
        # trailing dim (interpret mode has no such restriction).
        raise ValueError(
            f"flash requires blk_k % 128 == 0 on TPU (got {blk_k}); "
            "use attention() for automatic routing")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bias8 = _fold_bias(bias, b, h, t)
    if layout == "bthd":
        if d % _LANES and not _interpret():
            raise ValueError(
                f"layout='bthd' needs head dim % 128 == 0 on TPU "
                f"(got {d}) — the in-place head-chunk blocks are "
                "lane-aligned slices of [b, t, h*d]; transpose to "
                "bhtd for smaller head dims")
        out = _flash(q, k, v, bias8, blk_q, blk_k, bool(causal),
                     float(scale), True)
        return out.reshape(b, t, h, d)
    fold = lambda x: x.reshape(b * h, t, d)
    out = _flash(fold(q), fold(k), fold(v), bias8, blk_q, blk_k,
                 bool(causal), float(scale), False)
    return out.reshape(b, h, t, d)


# Below this sequence length the flash grid degenerates to one tiny
# block per (batch*head) and XLA's batched fused attention wins;
# attention() auto-routes.  The threshold dates from round-5 sweeps
# whose records are gone: where the crossover sits is not measured on
# today's code.
_FLASH_MIN_T = 512


def _auto_blocks(t: int, causal: bool = False):
    """Hand-picked blocks: (1024, 512) at flash-routed lengths, from
    round-5 sweeps whose records are gone (the top three configs —
    (1024,512), (512,1024), (512,512) — swapped ranks between runs
    there); not measured on today's code.  256-sized blocks are never
    PICKED here for t divisible by 512 (shorter t falls back to a
    single t-sized block — attention() routes those to XLA anyway).
    Single-step kernel when one K/V block covers the row."""
    bq = 1024 if t % 1024 == 0 else (512 if t % 512 == 0 else t)
    bk = 512 if t % 512 == 0 else t
    return min(bq, t), min(bk, t)


def _flash_applicable(q, k, bias, blk_q, blk_k) -> bool:
    if q.shape != k.shape:           # cross-attention / tq != tk
        return False
    t = q.shape[2]
    if t < _FLASH_MIN_T:             # XLA wins at short t (see above)
        return False
    bq, bk = min(blk_q, t), min(blk_k, t)
    if t % bq or t % bk or t % 8 or bk % _LANES:
        return False
    if max(bq, bk) > 1024:
        # a non-tiling t would clamp to one giant [t, t] block and
        # blow VMEM at compile time — fall back instead
        return False
    if bias is not None:
        bias = jnp.asarray(bias)
        if bias.ndim == 4 and bias.shape[2] != 1:
            return False             # query-dependent bias
        b = q.shape[0]
        if bias.ndim == 2 and bias.shape[0] != b:
            # a [tq, tk] mask is query-dependent, not the [b, tk]
            # key-position form — and when b == t the two are
            # indistinguishable by shape, so the routing contract is
            # strictly "dim 0 is batch" (callers with triangular
            # masks should pass causal=True instead)
            return False
        if bias.ndim == 3 and bias.shape[0] != b:
            return False
    return True


def mask_to_bias(mask):
    """[b, t] sequence mask (nonzero = valid) -> additive key-position
    bias (-1e9 at padded positions), or None passthrough."""
    if mask is None:
        return None
    return (1.0 - (mask > 0).astype(jnp.float32)) * -1e9


def xla_attention(q, k, v, bias=None, causal: bool = False,
                  scale: Optional[float] = None):
    """Plain XLA einsum attention over [b, h, tq, d] — the fallback the
    flash kernel routes to at short t (XLA's own fusion wins there) and
    the reference path the kernel tests compare against."""
    tq, d = q.shape[2], q.shape[3]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    ct = jnp.promote_types(q.dtype, jnp.float32)  # >=f32 softmax; f64
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(ct) * scale  # stays f64
    if bias is not None:
        bias = jnp.asarray(bias, ct)
        if bias.ndim == 2:                # [b, tk] key-position mask
            bias = bias[:, None, None, :]
        elif bias.ndim == 3:              # [b, h, tk]
            bias = bias[:, :, None, :]
        s = s + bias
    if causal:
        tk = k.shape[2]
        rows = lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        cols = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        s = jnp.where((cols <= rows)[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# Route-taken probe (round-3 review: "expose a route-taken probe on
# kernels.attention rather than trusting _flash_applicable").  Entries
# are appended at TRACE time — reset, force a fresh trace (new shapes
# or cleared jit cache), then inspect.  A cached executable records
# nothing: the log answers "what did the last compilation choose".
# Bounded (last 256 traces) so long-lived serving processes that
# retrace many shapes don't grow it without end; the deque stays a
# single-threaded debugging probe carrying (path, t, d) detail.  The
# PRODUCTION counter is flash_route_total{path=...} below: thread-safe,
# unbounded-in-time, scrapeable — a silent fallback off the flash path
# (long-t retrace routing to XLA) moves a metric a dashboard alerts on
# instead of hiding in a debug deque (ADVICE r4 thread-safety caveat
# resolved by the registry's per-child locks).
_ROUTE_LOG: collections.deque = collections.deque(maxlen=256)
_ROUTE_TOTAL = telemetry.counter(
    "flash_route_total",
    "attention() route decisions at trace time, by kernel path",
    labelnames=("path",))
_ROUTE_FLASH = _ROUTE_TOTAL.labels(path="flash")
_ROUTE_XLA = _ROUTE_TOTAL.labels(path="xla")
# long-t fallbacks specifically: the silent-regression alarm series
# (kept OUT of flash_route_total so that family's sum == total routes)
_ROUTE_XLA_LONG_T = telemetry.counter(
    "flash_fallback_above_threshold_total",
    "XLA fallbacks at t >= the flash threshold — should be 0; nonzero "
    "means a shape/bias/block constraint silently demoted a hot path")


def reset_route_log() -> None:
    _ROUTE_LOG.clear()


def route_log() -> tuple:
    """Tuple of ('flash'|'xla', t, d) per attention() trace since the
    last reset (bounded at the last 256 entries)."""
    return tuple(_ROUTE_LOG)


# GSPMD cannot partition a Mosaic kernel ("wrap the call in a
# shard_map" — the chip's compiler refuses the program; interpret mode
# never notices).  A caller that traces over a multi-device mesh
# declares it here — ShardedTrainer does — and attention() maps the
# flash call over the batch ('data') and head ('model') axes.
# Attention is independent across both, so the mapped kernel is exact.
_TRACE_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "dl4j_tpu_attention_trace_mesh", default=None)


@contextlib.contextmanager
def trace_mesh(mesh):
    """Declare the mesh the enclosed TRACE is partitioned over (a
    one-device mesh declares nothing)."""
    token = _TRACE_MESH.set(mesh if mesh.size > 1 else None)
    try:
        yield
    finally:
        _TRACE_MESH.reset(token)


def _mesh_axes(mesh, b: int, h: int):
    """(batch axis, head axis) the flash call maps over — each a mesh
    axis name or None — or None when it cannot: the mesh carries an
    axis other than 'data'/'model', or one that does not divide."""
    names = {n for n in mesh.axis_names if mesh.shape[n] > 1}
    if not names <= {"data", "model"}:
        return None
    ba = "data" if "data" in names else None
    ha = "model" if "model" in names else None
    if (ba and b % mesh.shape[ba]) or (ha and h % mesh.shape[ha]):
        return None
    return ba, ha


def _flash_on_mesh(mesh, axes, q, k, v, blk_q, blk_k, *, bias, causal,
                   scale, layout="bhtd"):
    """``flash_attention`` per (batch, head) shard of ``mesh``."""
    ba, ha = axes
    qspec = (P(ba, None, ha, None) if layout == "bthd"
             else P(ba, ha, None, None))
    if bias is None:
        bspec = None
    elif jnp.ndim(bias) == 2:                       # [b, tk]
        bspec = P(ba, None)
    else:                      # [b, h|1, tk] / [b, h|1, 1, tk]
        head = ha if jnp.shape(bias)[1] > 1 else None
        bspec = P(ba, head, *(None,) * (jnp.ndim(bias) - 2))

    def local(q, k, v, bias):
        return flash_attention(q, k, v, blk_q, blk_k, bias=bias,
                               causal=causal, scale=scale, layout=layout)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(qspec, qspec, qspec, bspec),
                         out_specs=qspec, check_vma=False)(q, k, v, bias)


def attention(q, k, v, bias=None, causal: bool = False,
              scale: Optional[float] = None, blk_q: Optional[int] = None,
              blk_k: Optional[int] = None, layout: str = "bhtd"):
    """General fused-attention entry: routes to the Pallas flash
    kernel when the shape/mask permits, else to ``xla_attention``
    (which XLA fuses well at short t).  ``layout="bthd"`` accepts
    [b, t, h, d] operands and keeps them transpose-free on the flash
    path (the XLA fallback transposes internally).  This is the op the
    graph IR's ``fused_attention`` lowers to (the importer rewrites
    matmul-softmax-matmul subgraphs into it)."""
    if layout == "bthd":
        tq, d = q.shape[1], q.shape[3]
        # normalized views for routing/fallback; dead (DCE'd) when the
        # flash path is taken
        qn, kn = jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)
    else:
        tq, d = q.shape[2], q.shape[3]
        qn, kn = q, k
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if blk_q is None or blk_k is None:
        abq, abk = _auto_blocks(tq, causal=causal)
        blk_q = blk_q or abq
        blk_k = blk_k or abk
    mesh = _TRACE_MESH.get()
    axes = (None if mesh is None
            else _mesh_axes(mesh, qn.shape[0], qn.shape[1]))
    if (mesh is None or axes is not None) and _flash_applicable(
            qn, kn, bias, blk_q, blk_k):
        _ROUTE_LOG.append(("flash", tq, d))
        _ROUTE_FLASH.inc()
        flash = (functools.partial(_flash_on_mesh, mesh, axes)
                 if mesh is not None else flash_attention)
        if layout == "bthd" and d % _LANES and not _interpret():
            # head dim too small for in-place head-chunk blocks:
            # transpose to the flat layout (exactly the pre-r5 cost)
            out = flash(qn, kn, jnp.swapaxes(v, 1, 2), blk_q, blk_k,
                        bias=bias, causal=causal, scale=scale)
            return jnp.swapaxes(out, 1, 2)
        return flash(q, k, v, blk_q, blk_k, bias=bias, causal=causal,
                     scale=scale, layout=layout)
    _ROUTE_LOG.append(("xla", tq, d))
    _ROUTE_XLA.inc()
    if tq >= _FLASH_MIN_T:
        _ROUTE_XLA_LONG_T.inc()
        # Fallback despite long t is NOT the expected short-t routing —
        # say why the flash kernel was skipped (round-3 review weak 1).
        log.warning(
            "attention: XLA fallback at t=%d (>= flash threshold %d) — "
            "shape/bias/block/mesh constraint failed (q=%s k=%s "
            "bias=%s blk=(%d,%d) mesh=%s)", tq, _FLASH_MIN_T, q.shape,
            k.shape, None if bias is None else jnp.shape(bias), blk_q,
            blk_k, None if mesh is None else dict(mesh.shape))
    else:
        log.info("attention: XLA route at t=%d (< flash threshold %d; "
                 "XLA's own fusion wins at short t)", tq, _FLASH_MIN_T)
    if layout == "bthd":
        out = xla_attention(qn, kn, jnp.swapaxes(v, 1, 2), bias=bias,
                            causal=causal, scale=scale)
        return jnp.swapaxes(out, 1, 2)
    return xla_attention(q, k, v, bias=bias, causal=causal, scale=scale)
