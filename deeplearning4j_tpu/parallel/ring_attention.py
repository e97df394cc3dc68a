"""Ring attention: sequence/context parallelism over the mesh.

The capability the reference NEVER had (its only long-sequence mechanism
is truncated BPTT — SURVEY §5.7): exact attention over sequences sharded
across devices.  Each device holds one block of Q and one block of K/V;
K/V blocks rotate around the ring via ``lax.ppermute`` over ICI while a
flash-style running softmax (running max / denominator / weighted
accumulator) folds each incoming block in — memory per device is
O(t_local²) per step instead of O(t²), and the permute overlaps with the
block matmuls.

API: ``ring_attention(q, k, v, mask=None, axis_name="sequence")`` is the
per-shard function for use INSIDE ``shard_map``;
``ring_self_attention(mesh, q, k, v, mask=None)`` wraps the shard_map
over a mesh with a 'sequence' axis (batch over 'data' when present).
Gradients flow through the collective (jax differentiates ppermute), so
the same function serves training.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _block_attend(q, k, v, mask_k, m, l, o):
    """Fold one K/V block into the running softmax state.

    q [b, h, tq, d]; k/v [b, h, tk, d]; mask_k [b, tk] or None;
    m, l [b, h, tq]; o [b, h, tq, d].
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype))
    if mask_k is not None:
        neg = jnp.asarray(-1e30, s.dtype)
        s = jnp.where(mask_k[:, None, None, :] > 0, s, neg)
    m_new = jnp.maximum(m, s.max(-1))
    scale = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * scale + p.sum(-1)
    o_new = o * scale[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, o_new


def ring_attention(q, k, v, mask: Optional[jnp.ndarray] = None,
                   axis_name: str = "sequence"):
    """Per-shard exact attention with K/V rotation (call inside
    shard_map).  q/k/v: [b, h, t_local, d]; mask: [b, t_local] keyed to
    THIS shard's keys.  Returns [b, h, t_local, d]."""
    n = lax.psum(1, axis_name)
    # Initial carries are DERIVED from q/k so they carry the same
    # varying-manual-axes type as the loop outputs (jax's shard_map vma
    # tracking rejects unvarying-in / varying-out scan carries).
    m0 = q[..., 0] * 0 - jnp.inf          # [b, h, tq]
    l0 = q[..., 0] * 0
    o0 = jnp.zeros_like(q)
    perm = [(i, (i + 1) % n) for i in range(n)]
    if mask is None:
        # all-ones mask keeps ONE carry structure (None can't ride a
        # fori_loop carry); XLA folds the no-op where() away.
        mask = jnp.ones((q.shape[0], k.shape[2]), q.dtype)
    mask = mask.astype(q.dtype) * (k[:, 0, :, 0] * 0 + 1)

    def body(_, carry):
        m, l, o, k_blk, v_blk, mask_blk = carry
        m, l, o = _block_attend(q, k_blk, v_blk, mask_blk, m, l, o)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        mask_blk = lax.ppermute(mask_blk, axis_name, perm)
        return m, l, o, k_blk, v_blk, mask_blk

    m, l, o, *_ = lax.fori_loop(0, n, body, (m0, l0, o0, k, v, mask))
    return o / jnp.maximum(l, 1e-30)[..., None]


def ring_self_attention(mesh: Mesh, q, k, v,
                        mask: Optional[jnp.ndarray] = None):
    """shard_map wrapper: q/k/v [b, h, t, d] sharded over the mesh's
    'sequence' axis on t (and 'data' on b when the mesh has one)."""
    batch_ax = "data" if "data" in mesh.axis_names else None
    qkv_spec = P(batch_ax, None, "sequence", None)
    mask_spec = P(batch_ax, "sequence")
    in_specs = (qkv_spec, qkv_spec, qkv_spec,
                mask_spec if mask is not None else None)
    fn = partial(ring_attention, axis_name="sequence")

    if mask is None:
        def shard_fn(q_, k_, v_):
            return fn(q_, k_, v_, None)
        mapped = jax.shard_map(shard_fn, mesh=mesh,
                               in_specs=in_specs[:3], out_specs=qkv_spec)
        return mapped(q, k, v)

    def shard_fn(q_, k_, v_, mask_):
        return fn(q_, k_, v_, mask_)
    mapped = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                           out_specs=qkv_spec)
    return mapped(q, k, v, mask)


def full_attention_reference(q, k, v, mask=None):
    """Single-device reference (for tests/benchmarks)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype))
    if mask is not None:
        s = jnp.where(mask[:, None, None, :] > 0,
                      s, jnp.asarray(-1e30, s.dtype))
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
