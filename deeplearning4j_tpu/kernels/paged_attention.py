"""Paged decode attention — K/V read through a block table.

The serving memory model (PR 7): instead of each decode slot owning a
contiguous ``[max_len]`` KV stripe, K/V live in a global pool of
fixed-size blocks (``block_size`` tokens each) and every slot carries a
``[max_blocks]`` int32 **block table** mapping its logical positions
onto pool blocks.  A short request pins ``ceil(len/block_size)`` blocks
instead of a whole stripe, and identical prompt prefixes SHARE blocks
(the vLLM paged-attention layout, expressed Pallas-side the way the
flash kernel expresses streaming softmax).

Two implementations behind one router (the flash-attention
``attention()`` pattern — ``paged_route_total{path=}`` counts the
decision at trace time):

* ``paged_decode_attention_reference`` — pure JAX: gather the table's
  blocks into the slot's contiguous ``[L, dh]`` view with ``jnp.take``
  and run EXACTLY the stripe decode-step math (f32 scores, -1e9 mask,
  f32 softmax).  This is the parity path: greedy decode through it is
  byte-identical to the stripe layout, which is what lets the server's
  offline-parity invariant survive the paged rewrite.  CPU tier-1
  always routes here.
* ``_paged_verify_pallas`` — a Pallas TPU kernel, grid (B, max_blocks),
  W query rows per slot (``_paged_decode_pallas`` is its W == 1 case):
  the block table rides as a SCALAR-PREFETCH operand so each K/V block
  DMA is issued straight out of the table entry (no gathered [B, L]
  copy of the pool ever materializes in HBM), with the flash-style
  running (max, denom, accumulator) recurrence in VMEM scratch across
  the block axis and lane-replicated row stats.  Out-of-context blocks
  (``kb * bs > pos``) skip their matmuls entirely.  Ideal shapes are
  the usual Mosaic ones (dh a multiple of 128); correctness at any
  shape is exercised under ``interpret=True``, and
  ``tests/test_chip_compile.py`` holds both entries to the chip's
  compiler at the served widths.

Scratch block 0 is the pool's write sink for masked-inactive slots —
never referenced by a live table entry, so its contents are garbage by
design and must never be read unmasked.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.kernels.flash_attention import (_NEG, _LANES,
                                                        _dimsem,
                                                        _interpret)

_ROUTE_TOTAL = telemetry.counter(
    "paged_route_total",
    "paged_decode_attention route decisions at trace time, by path",
    labelnames=("path",))
_ROUTE_PALLAS = _ROUTE_TOTAL.labels(path="pallas")
_ROUTE_REFERENCE = _ROUTE_TOTAL.labels(path="reference")
# a tp>1 shard ctx forces the reference path even on TPU: pallas_call
# is opaque to GSPMD (it would gather the full pool per device and
# compute every head), while the reference gather/einsum partitions
# along the sharded head axis for free.  A shard_map'd kernel over the
# local head shard is the recorded remainder.
_ROUTE_REFERENCE_TP = _ROUTE_TOTAL.labels(path="reference_tp")


def paged_gather(pool, block_table):
    """[n_blocks, h, bs, dh] pool + [B, max_blocks] table -> the
    per-slot contiguous [B, h, max_blocks*bs, dh] view (the stripe the
    table logically describes).  Unallocated table entries point at the
    scratch block 0 — callers must mask those positions."""
    B, mb = block_table.shape
    _, h, bs, dh = pool.shape
    lin = jnp.take(pool, block_table, axis=0)        # [B, mb, h, bs, dh]
    return lin.transpose(0, 2, 1, 3, 4).reshape(B, h, mb * bs, dh)


def paged_decode_attention_reference(q, k_pool, v_pool, block_table,
                                     pos, scale: float):
    """One-query-per-slot attention through the block table, stripe
    math: gather the table into the contiguous view, then the same
    f32-score / -1e9-mask / f32-softmax sequence as the stripe decode
    step (``_block_decode_step``) — byte parity with offline decode
    depends on mirroring it exactly."""
    kl = paged_gather(k_pool, block_table)
    vl = paged_gather(v_pool, block_table)
    L = kl.shape[2]
    qq = q[:, :, None, :]                            # [B, h, 1, dh]
    s = jnp.einsum("bhqd,bhkd->bhqk", qq, kl).astype(jnp.float32)
    s = s * scale
    valid = (jnp.arange(L)[None, :] <= pos[:, None])[:, None, None, :]
    s = jnp.where(valid, s, -1e9)
    p = jax.nn.softmax(s, axis=-1).astype(vl.dtype)
    att = jnp.einsum("bhqk,bhkd->bhqd", p, vl)
    return att[:, :, 0, :]


def _paged_decode_pallas(q, k_pool, v_pool, block_table, pos,
                         scale: float):
    """One query row per slot: the W == 1 case of the verify kernel.
    A ``[h, dh] x [h, bs, dh]`` batched mat-vec has no free lhs
    dimension and Mosaic refuses it; the verify kernel's
    ``[h, W, dh]`` query block gives the product one."""
    return _paged_verify_pallas(q[:, None], k_pool, v_pool,
                                block_table, pos, scale)[:, 0]


def paged_verify_attention_reference(q, k_pool, v_pool, block_table,
                                     pos0, scale: float):
    """W-query verification attention through the block table, stripe
    math, UNROLLED per query row: query row j of slot b sits at
    position ``pos0[b] + j`` and attends over positions <= its own.

    The unroll is the parity contract, not a style choice: each row
    runs EXACTLY the single-query decode step's einsum/softmax shapes
    ([B, h, 1, L]) against the once-gathered table view, because a
    W-row score einsum regroups XLA's head-dim reduction and drifts
    from the sequential decode ticks by ulps (measured on CPU — the
    same lesson PR 7 learned about padded key gathers).  Rows write
    nothing here; the caller has already scattered the chunk's K/V
    into the pool, and the causal mask hides in-chunk future rows the
    way it hides stale stripe tails in the decode step."""
    kl = paged_gather(k_pool, block_table)
    vl = paged_gather(v_pool, block_table)
    L = kl.shape[2]
    W = q.shape[1]
    cols = jnp.arange(L)[None, :]
    rows = []
    for j in range(W):
        qq = q[:, j][:, :, None, :]                  # [B, h, 1, dh]
        s = jnp.einsum("bhqd,bhkd->bhqk", qq, kl).astype(jnp.float32)
        s = s * scale
        valid = (cols <= (pos0 + j)[:, None])[:, None, None, :]
        s = jnp.where(valid, s, -1e9)
        p = jax.nn.softmax(s, axis=-1).astype(vl.dtype)
        rows.append(jnp.einsum("bhqk,bhkd->bhqd", p, vl)[:, :, 0, :])
    return jnp.stack(rows, axis=1)                   # [B, W, h, dh]


def _lane_bcast3(stat, width):
    """3-D variant of the flash module's ``_lane_bcast`` for
    [h, W, _LANES] running stats (W rides the sublane axis)."""
    if width % _LANES == 0:
        return jnp.tile(stat, (1, 1, width // _LANES))
    return stat[:, :, :1] if width > _LANES else stat[:, :, :width]


def _verify_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bs: int, mb: int, W: int,
                   scale: float):
    """Grid (B, max_blocks), block axis minor/arbitrary: per slot,
    stream the table's K/V blocks through VMEM with the running softmax
    state in scratch, W query rows per slot — query row w sits at
    position pos0 + w, so the in-block causal mask compares each key's
    position against a per-row query position.  Blocks past the
    DEEPEST query's context skip compute entirely."""
    b, kb = pl.program_id(0), pl.program_id(1)
    h, dh = q_ref.shape[1], q_ref.shape[3]
    @pl.when(kb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    p0 = pos_ref[b]

    @pl.when(kb * bs <= p0 + W - 1)
    def _compute():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]   # (h, W, dh), (h, bs, dh)
        s = lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale    # (h, W, bs)
        j = kb * bs + lax.broadcasted_iota(jnp.int32, (h, W, bs), 2)
        qp = p0 + lax.broadcasted_iota(jnp.int32, (h, W, bs), 1)
        s = jnp.where(j <= qp, s, _NEG)
        m_prev, l_prev = m_ref[:], l_ref[:]                # (h, W, 128)
        m_new = jnp.maximum(m_prev,
                            jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - _lane_bcast3(m_new, bs))
        corr = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * corr + jnp.sum(p, axis=2, keepdims=True)
        pv = lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # (h, W, dh)
        acc_ref[:] = acc_ref[:] * _lane_bcast3(corr, dh) + pv

    @pl.when(kb == mb - 1)
    def _finish():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)   # masked rows only
        out = (acc_ref[:] / _lane_bcast3(l_safe, dh)).astype(o_ref.dtype)
        o_ref[0] = out.transpose(1, 0, 2)      # (h, W, dh) -> (W, h, dh)


# the outer scope keeps the kernel's ``name=`` whole under any
# transformation (``%paged_attention.N`` in a profile): see the note
# at flash_attention._flash_fwd
@jax.named_scope("paged_read")
def _paged_verify_pallas(q, k_pool, v_pool, block_table, pos0,
                         scale: float):
    B, W, h, dh = q.shape
    bs = k_pool.shape[2]
    mb = block_table.shape[1]
    qh = q.transpose(0, 2, 1, 3)               # (B, h, W, dh)
    kv_spec = pl.BlockSpec(
        (1, h, bs, dh), lambda b, kb, tbl, p: (tbl[b, kb], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, mb),
        in_specs=[
            pl.BlockSpec((1, h, W, dh),
                         lambda b, kb, tbl, p: (b, 0, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, W, h, dh),
                               lambda b, kb, tbl, p: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, W, _LANES), jnp.float32),  # running max
            pltpu.VMEM((h, W, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((h, W, dh), jnp.float32),      # output acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_verify_kernel, bs=bs, mb=mb, W=W,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, W, h, dh), q.dtype),
        compiler_params=_dimsem("parallel", "arbitrary"),
        interpret=_interpret(),
        name="paged_attention",
    )(block_table, pos0, qh, k_pool, v_pool)


def paged_verify_attention(q, k_pool, v_pool, block_table, pos0,
                           scale: Optional[float] = None, shard=None):
    """softmax(q . K_table^T) V_table for a CHUNK of W query tokens
    per slot — the speculative verification read: query row j of slot
    b is the j-th token of the verified chunk, at position
    ``pos0[b] + j``, attending over every position <= its own
    (in-chunk earlier rows included; the caller scatters the whole
    chunk's K/V into the pool before this read, exactly as the decode
    tick writes-then-reads its single row).

    ``q`` [B, W, h, dh]; pools / table / scale as
    :func:`paged_decode_attention`; ``pos0`` [B] int32.  Routes to the
    multi-query Pallas kernel on TPU, else to the per-row-unrolled
    reference — the byte-parity path the speculative greedy-parity
    tests pin (CPU tier-1 always exercises it).  ``shard`` (a
    ``TpShardCtx`` with ``tp > 1``) also forces the reference path:
    its gathers/einsums partition along the sharded head axis, where
    the Pallas call is opaque to GSPMD."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    tp_forced = shard is not None and shard.tp > 1
    if _route() == "pallas" and not tp_forced:
        _ROUTE_PALLAS.inc()
        return _paged_verify_pallas(q, k_pool, v_pool, block_table,
                                    pos0, float(scale))
    (_ROUTE_REFERENCE_TP if tp_forced else _ROUTE_REFERENCE).inc()
    return paged_verify_attention_reference(q, k_pool, v_pool,
                                            block_table, pos0,
                                            float(scale))


def _route() -> str:
    """'pallas' | 'reference' — trace-time decision.  CPU/interpret
    backends take the reference path (it is the byte-parity contract
    the server's offline-parity tests enforce); TPU takes the kernel.
    ``DL4J_TPU_PAGED_KERNEL=reference|pallas`` overrides for debugging
    (pallas off-TPU runs under interpret mode)."""
    forced = os.environ.get("DL4J_TPU_PAGED_KERNEL", "")
    if forced in ("reference", "pallas"):
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def paged_decode_attention(q, k_pool, v_pool, block_table, pos,
                           scale: Optional[float] = None, shard=None):
    """softmax(q . K_table^T) V_table for ONE query token per slot.

    ``q`` [B, h, dh] — the just-written token's query per slot;
    ``k_pool``/``v_pool`` [n_blocks, h, block_size, dh] — the global
    block pool (block 0 is the scratch sink); ``block_table``
    [B, max_blocks] int32; ``pos`` [B] int32 — attend over positions
    <= pos (the row written this tick included).  Routes to the Pallas
    kernel on TPU, else to the gather-based reference (the byte-parity
    path CPU tier-1 exercises).  ``shard`` with ``tp > 1`` forces the
    reference path (see :func:`paged_verify_attention`)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    tp_forced = shard is not None and shard.tp > 1
    if _route() == "pallas" and not tp_forced:
        _ROUTE_PALLAS.inc()
        return _paged_decode_pallas(q, k_pool, v_pool, block_table,
                                    pos, float(scale))
    (_ROUTE_REFERENCE_TP if tp_forced else _ROUTE_REFERENCE).inc()
    return paged_decode_attention_reference(q, k_pool, v_pool,
                                            block_table, pos,
                                            float(scale))
