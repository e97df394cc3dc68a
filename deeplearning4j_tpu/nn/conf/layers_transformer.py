"""Transformer layers — the flagship TPU model family.

The reference has no native transformer blocks (its BERT story is
TF-import only — ``samediff-import-tensorflow`` [UNVERIFIED]); these
layers are the framework-native equivalent, built so the whole encoder
stack compiles to one XLA program with the Pallas flash-attention
kernel in the hot path (``kernels/flash_attention.py``).

``EmbeddingSequenceLayer`` extends DL4J's
``org.deeplearning4j.nn.conf.layers.EmbeddingSequenceLayer``
[UNVERIFIED] (ids -> vectors) with learned positional embeddings and
embedding layer-norm, i.e. a BERT input block.

``TransformerEncoderBlock`` is one post-LN encoder layer (attention +
FFN, residuals, layer norms) — matmul-dominated, bf16-friendly, the
shape the MXU wants.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.conf.base import BaseLayerConf, register_layer
from deeplearning4j_tpu.nn.conf.layers_core import apply_dropout
from deeplearning4j_tpu.nn.weights_init import init_weights


def _layer_norm(x, gamma, beta, eps=1e-12):
    """LN at >=f32 (bf16 variance is numerically unsafe; f64 stays f64
    for the gradient-check harness), output in x dtype."""
    ct = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(ct)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(ct) + beta.astype(ct)).astype(x.dtype)


@register_layer
@dataclasses.dataclass
class EmbeddingSequenceLayer(BaseLayerConf):
    """[b, t] int ids -> [b, t, n_out] vectors: word embedding +
    (optional) learned positional embedding + (optional) layer norm —
    the BERT input block in one layer."""

    n_in: Optional[int] = None       # vocabulary size
    n_out: Optional[int] = None      # embedding dim
    max_len: int = 512               # positional table length
    add_positional: bool = True
    layer_norm: bool = True
    eps: float = 1e-12

    WANTED_KINDS = ("any",)
    OUTPUT_KIND = "rnn"

    def infer_shapes(self, input_shape):
        t = input_shape[0] if input_shape else self.max_len
        return (t, self.n_out)

    def has_params(self):
        return True

    def init(self, key, dtype=jnp.float32):
        kw, kp = jax.random.split(key)
        params = {"W": init_weights(kw, (self.n_in, self.n_out), self.n_in,
                                    self.n_out, self.weight_init, dtype,
                                    self.weight_distribution)}
        if self.add_positional:
            params["P"] = 0.02 * jax.random.normal(
                kp, (self.max_len, self.n_out), dtype)
        if self.layer_norm:
            params["g"] = jnp.ones((self.n_out,), dtype)
            params["b"] = jnp.zeros((self.n_out,), dtype)
        return params, {}

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        w = params["W"]
        if compute_dtype is not None:
            w = w.astype(compute_dtype)
        y = jnp.take(w, idx, axis=0)               # [b, t, d]
        if self.add_positional:
            t = y.shape[1]
            y = y + params["P"][:t].astype(y.dtype)[None]
        if self.layer_norm:
            y = _layer_norm(y, params["g"], params["b"], self.eps)
        return apply_dropout(y, self.dropout, training, rng), state


@register_layer
@dataclasses.dataclass
class TransformerEncoderBlock(BaseLayerConf):
    """One post-LN transformer encoder layer over [b, t, d]:

        h = LN(x + Wo·FlashAttention(Wq x, Wk x, Wv x))
        y = LN(h + W2·act(W1 h))

    Attention runs through ``kernels.attention`` — the Pallas flash
    kernel on TPU (O(t) memory, causal/mask-aware) with an XLA einsum
    fallback; a [b, t] sequence mask becomes the kernel's additive
    key-position bias.  With ``compute_dtype=bfloat16`` every matmul is
    full-rate MXU; layer norms and softmax stay f32.

    For decoding the block is written ONCE more, as ``sequence()`` (its
    causal forward over whole sequences: the prefill) and ``step()``
    (its forward for new rows against a cache), the cache access handed
    in -- the seam ``models.generation.TransformerGenerator`` walks, as
    it walks the pre-norm runs of ``layers_hybrid``.  ``apply()`` shares
    nothing with them: flash, dropout and the padding mask are its
    own."""

    n_heads: int = 8
    d_ff: Optional[int] = None       # default 4*d
    n_in: Optional[int] = None
    n_out: Optional[int] = None
    causal: bool = False
    eps: float = 1e-12
    use_flash: bool = True

    WANTED_KINDS = ("rnn",)
    USES_MASK = True
    RECURRENT = False                # a decode keeps K/V rows only
    #: why a server cannot share, restore, re-verify or shard this
    #: kind's K/V rows (None: it can)
    REFUSES = None

    def infer_shapes(self, input_shape):
        t, f = input_shape
        self.n_in = int(f)
        self.n_out = int(f)
        if self.d_ff is None:
            self.d_ff = 4 * self.n_in
        if self.n_in % self.n_heads:
            raise ValueError(
                f"d_model {self.n_in} must divide by n_heads {self.n_heads}")
        return (t, self.n_out)

    def has_params(self):
        return True

    def init(self, key, dtype=jnp.float32):
        d, ff = self.n_in, self.d_ff
        ks = jax.random.split(key, 6)
        mk = lambda k, shape: init_weights(k, shape, shape[0], shape[-1],
                                           self.weight_init, dtype,
                                           self.weight_distribution)
        params = {
            "Wqkv": mk(ks[0], (d, 3 * d)),   # fused qkv projection
            "bqkv": jnp.zeros((3 * d,), dtype),
            "Wo": mk(ks[1], (d, d)), "bo": jnp.zeros((d,), dtype),
            "ln1_g": jnp.ones((d,), dtype), "ln1_b": jnp.zeros((d,), dtype),
            "W1": mk(ks[2], (d, ff)), "b1": jnp.zeros((ff,), dtype),
            "W2": mk(ks[3], (ff, d)), "b2": jnp.zeros((d,), dtype),
            "ln2_g": jnp.ones((d,), dtype), "ln2_b": jnp.zeros((d,), dtype),
        }
        return params, {}

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None, mask=None):
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        cast = lambda w: w.astype(x.dtype)
        r1 = r2 = None
        if rng is not None:
            r1, r2 = jax.random.split(rng)
        b, t, d = x.shape
        h, dh = self.n_heads, d // self.n_heads

        from deeplearning4j_tpu.kernels import (
            attention, mask_to_bias, xla_attention)
        qkv = x @ cast(params["Wqkv"]) + cast(params["bqkv"])
        q, k, v = jnp.split(qkv, 3, axis=-1)
        bias = mask_to_bias(mask)
        if self.use_flash:
            # [b, t, h, dh] straight into the kernel's bthd layout —
            # the [b, h, t, dh] transpose pair cost ~22 ms/step on
            # zoo.Gpt (fwd+bwd, r5 profile) and is gone entirely
            split = lambda z: z.reshape(b, t, h, dh)
            att = attention(split(q), split(k), split(v), bias=bias,
                            causal=self.causal, layout="bthd")
        else:
            split = lambda z: z.reshape(b, t, h, dh).transpose(
                0, 2, 1, 3)
            att = xla_attention(split(q), split(k), split(v),
                                bias=bias, causal=self.causal)
            att = att.transpose(0, 2, 1, 3)
        att = att.reshape(b, t, d)
        att = att @ cast(params["Wo"]) + cast(params["bo"])
        att = apply_dropout(att, self.dropout, training, r1)
        hdn = _layer_norm(x + att, params["ln1_g"], params["ln1_b"],
                          self.eps)

        act = get_activation(self.activation or "gelu")
        ffn = act(hdn @ cast(params["W1"]) + cast(params["b1"]))
        ffn = ffn @ cast(params["W2"]) + cast(params["b2"])
        ffn = apply_dropout(ffn, self.dropout, training, r2)
        y = _layer_norm(hdn + ffn, params["ln2_g"], params["ln2_b"],
                        self.eps)
        return y, state

    # -- the decoding forms ------------------------------------------------
    n_kv_heads = property(lambda self: self.n_heads)
    head_dim = property(lambda self: self.n_in // self.n_heads)

    def _qkv(self, p, x):
        """x [..., d] -> q, k, v [..., n_heads, head_dim]."""
        qkv = x @ p["Wqkv"].astype(x.dtype) + p["bqkv"].astype(x.dtype)
        lead = x.shape[:-1] + (self.n_heads, self.head_dim)
        return tuple(z.reshape(lead) for z in jnp.split(qkv, 3, axis=-1))

    def _tail(self, p, x, att, shard):
        """``Wo`` + residual + ``ln1`` + feed-forward + ``ln2`` over
        attention's output ``att`` (shaped like x).  ``shard`` (a
        ``parallel.mesh.TpShardCtx``, or None = identity) is the
        mesh-sharded parity contract: weights arrive with their OUTPUT
        columns sharded along ``tp`` (heads ride along when qkv splits)
        and ``shard.rep`` gathers the feature axis back to full
        replication at EXACTLY the points where the math reduces over
        it -- before ``@ Wo``, both layer norms and ``@ W2`` -- so no
        device sums a partial axis."""
        rep = shard.rep if shard is not None else (lambda t: t)
        cast = lambda w: w.astype(x.dtype)
        att = rep(att) @ cast(p["Wo"]) + cast(p["bo"])
        hdn = _layer_norm(rep(x + att), p["ln1_g"], p["ln1_b"], self.eps)
        act = get_activation(self.activation or "gelu")
        ffn = act(hdn @ cast(p["W1"]) + cast(p["b1"]))
        ffn = rep(ffn) @ cast(p["W2"]) + cast(p["b2"])
        return _layer_norm(rep(hdn + ffn), p["ln2_g"], p["ln2_b"], self.eps)

    def step(self, p, x, attend, shard=None):
        """x [rows, d], one new token a row: rows are slots in a decode
        tick and ``B * W`` flat rows in a speculative verify (2-D
        matmuls are row-bitwise-stable where a [B, W, d] contraction
        need not be).  ``attend(q, k, v [rows, n_heads, head_dim]) ->
        (att, cache)`` writes the rows' K/V and reads their context: a
        dense cache offline, the paged pool in the server.  Returns
        (y [rows, d], cache)."""
        att, cache = attend(*self._qkv(p, x))
        return self._tail(p, x, att.reshape(x.shape), shard), cache

    def sequence(self, p, x, t0=None, prefix=None, shard=None):
        """Whole-sequence causal forward, ONE batched pass: x [b, t, d]
        -> (y, {"k", "v"} [b, n_heads, t, head_dim], the rows as a pool
        holds them).  float32 scores, a -1e9 mask, softmax in float32:
        the math of ``step()``'s dense cache, so a prefill and t cached
        steps agree byte for byte.  Causal, so ``t0`` (a padded prompt's
        real length) changes nothing a real position reads.

        With ``prefix = (pk, pv [b, n_heads, P, head_dim], p0)`` the
        rows of x are the UNCACHED suffix at positions p0.. and the keys
        are [cached prefix ; suffix] (prefix columns >= p0 are padding
        and masked).  Masked columns contribute EXACT zeros to the
        softmax, so the suffix rows equal the whole prompt's: the
        prefix-cache hit path's parity contract.  k / v are then the
        suffix's rows only."""
        b, t, d = x.shape
        q, k, v = (z.transpose(0, 2, 1, 3) for z in self._qkv(p, x))
        rows = jnp.arange(t)[:, None]
        if prefix is None:
            kk, vv = k, v
            mask = jnp.arange(t)[None, :] <= rows
        else:
            pk, pv, p0 = prefix
            n = pk.shape[2]
            kk = jnp.concatenate([pk, k], axis=2)       # [b, h, P+t, dh]
            vv = jnp.concatenate([pv, v], axis=2)
            cols = jnp.arange(n + t)
            col_g = jnp.where(cols < n, cols, p0 + cols - n)  # global pos
            col_ok = jnp.where(cols < n, cols < p0, True)     # prefix pad
            mask = col_ok[None, :] & (col_g[None, :] <= p0 + rows)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk).astype(jnp.float32) \
            * (1.0 / (self.head_dim ** 0.5))
        s = jnp.where(mask[None, None], s, -1e9)
        w = jax.nn.softmax(s, axis=-1).astype(vv.dtype)
        att = jnp.einsum("bhqk,bhkd->bhqd", w, vv)
        att = att.transpose(0, 2, 1, 3).reshape(b, t, d)
        return self._tail(p, x, att, shard), {"k": k, "v": v}
