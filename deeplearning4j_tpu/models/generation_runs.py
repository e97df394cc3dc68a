"""Incremental decoding over a stack of block RUNS of two kinds.

``TransformerGenerator(net)`` returns this generator for a net built as
``EmbeddingSequenceLayer -> runs -> TiedLMHead`` where every run is a
``MambaBlockRun`` or an ``AttentionBlockRun``
(``nn/conf/layers_hybrid.py``; e.g. ``zoo.HybridDecoder``).  Beside
the K/V cache of its attention layers the decode carries a fixed-size
RECURRENT state per row for its Mamba layers:

    rec = {"h":    [rec_layers, b, d_state, d_inner] float32,
           "conv": [rec_layers, b, d_conv - 1, d_inner] compute dtype}

Nothing of a block is written here: prefill scans each run's
``sequence()`` and a decode tick scans each run's ``step()``, handing
the cache or state access in -- a dense cache offline (``_step``), the
paged pool in ``GenerationServer`` (``_step_paged``).  The parameters
of a run are stacked as the net holds them, so a snapshot of them is
the tree itself: ``_stack_blocks`` copies nothing.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.generation import (TransformerGenerator,
                                                  _cast_floating,
                                                  _draw_token,
                                                  _embed_prompt,
                                                  _embed_token)
from deeplearning4j_tpu.nn.conf.layers_hybrid import (AttentionBlockRun,
                                                      MambaBlockRun,
                                                      TiedLMHead)
from deeplearning4j_tpu.nn.conf.layers_transformer import \
    EmbeddingSequenceLayer

RUN_KINDS = (MambaBlockRun, AttentionBlockRun)


def _layer_of(stacked, layer):
    """Layer ``layer`` (a traced index) of a [layers, ...] array."""
    return jax.lax.dynamic_index_in_dim(stacked, layer, 0, keepdims=False)


def _with_layer(stacked, value, layer):
    return jax.lax.dynamic_update_index_in_dim(stacked, value, layer, 0)


def is_run_stack(net) -> bool:
    layers = list(net.layers)
    return len(layers) > 2 and all(isinstance(l, RUN_KINDS)
                                   for l in layers[1:-1])


class RunsGenerator(TransformerGenerator):
    """See the module docstring; the public surface is
    ``TransformerGenerator``'s (``generate()``)."""

    def __init__(self, net, compute_dtype=None):
        layers = list(net.layers)
        if not isinstance(layers[0], EmbeddingSequenceLayer):
            raise ValueError("generator expects EmbeddingSequenceLayer "
                             f"first, got {type(layers[0]).__name__}")
        if not isinstance(layers[-1], TiedLMHead):
            raise ValueError("a stack of block runs decodes through a "
                             f"TiedLMHead, got {type(layers[-1]).__name__}")
        self.net, self.emb, self.head = net, layers[0], layers[-1]
        self.runs = self.blocks = layers[1:-1]
        attn = [r for r in self.runs if not r.RECURRENT]
        rec = [r for r in self.runs if r.RECURRENT]
        # one pool and one stacked state serve every run of a kind
        if len({(r.n_kv_heads, r.head_dim) for r in attn}) > 1:
            raise ValueError("the attention runs of one stack share one "
                             "K/V pool: n_kv_heads and head_dim must agree")
        if len({(r.d_state, r.d_inner, r.d_conv) for r in rec}) > 1:
            raise ValueError("the recurrent runs of one stack share one "
                             "state: d_state, d_inner and d_conv must agree")
        if not attn:
            raise ValueError("a stack without an attention run has no "
                             "K/V pool to page (not supported)")
        self._attn, self._rec = attn[0], (rec[0] if rec else None)
        self.compute_dtype = (jnp.dtype(compute_dtype)
                              if compute_dtype else jnp.float32)
        self._fn_cache = {}

    # -- what a server sizes its pool and state by ----------------------
    @property
    def kv_layers(self) -> int:
        return sum(r.n_blocks for r in self.runs if not r.RECURRENT)

    @property
    def kv_heads(self) -> int:
        return self._attn.n_kv_heads

    @property
    def head_dim(self) -> int:
        return self._attn.head_dim

    @property
    def vocab_size(self) -> int:
        return int(self.emb.n_in)

    @property
    def recurrent(self):
        if self._rec is None:
            return None
        r = self._rec
        return {"layers": sum(x.n_blocks for x in self.runs if x.RECURRENT),
                "d_state": r.d_state, "d_inner": r.d_inner,
                "window": r.d_conv - 1}

    def fresh_rec(self, b: int):
        """The recurrent state of ``b`` rows that have seen nothing."""
        r = self.recurrent
        return {"h": jnp.zeros((r["layers"], b, r["d_state"], r["d_inner"]),
                               jnp.float32),
                "conv": jnp.zeros((r["layers"], b, r["window"],
                                   r["d_inner"]), self.compute_dtype)}

    @staticmethod
    def _stack_blocks(blk_ps):
        """The runs' parameters are stacked as the net holds them."""
        return tuple(blk_ps)

    def _logits(self, emb_p, head_p, x):
        return self.head.logits(head_p, emb_p["W"], x)

    # -- one tick --------------------------------------------------------
    def _tick(self, emb_p, runs_p, head_p, tok, pos, rec, active, kv,
              attend_at):
        """Every run's ``step()`` in turn.  ``kv`` is the attention
        layers' cache, threaded through ``attend_at(kv, layer)(q, k, v)
        -> (att, kv)``."""
        x = _embed_token(self.emb, emb_p, tok, pos).astype(
            self.compute_dtype)
        kv_l = rec_l = 0
        for run, p in zip(self.runs, runs_p):
            layers = jnp.arange(run.n_blocks)
            if run.RECURRENT:
                def body(carry, xs, run=run):
                    h, rec = carry
                    return run.step(xs[0], h, rec, xs[1], active), None
                (x, rec), _ = jax.lax.scan(body, (x, rec),
                                           (p, rec_l + layers))
                rec_l += run.n_blocks
            else:
                def body(carry, xs, run=run):
                    h, kv = carry
                    return run.step(xs[0], h, attend_at(kv, xs[1])), None
                (x, kv), _ = jax.lax.scan(body, (x, kv),
                                          (p, kv_l + layers))
                kv_l += run.n_blocks
        return self._logits(emb_p, head_p, x), kv, rec

    def _step(self, emb_p, runs_p, head_p, kc, vc, rec, tok, pos):
        """One offline decode tick over dense caches ``kc`` / ``vc``
        [kv_layers, b, kv_heads, L, head_dim]; ``pos`` a scalar."""
        scale = 1.0 / math.sqrt(self.head_dim)

        def attend_at(kv, layer):
            def attend(q, k, v):
                b, hq, dh = q.shape
                kc, vc = kv
                put = lambda c, row: jax.lax.dynamic_update_slice(
                    _layer_of(c, layer),
                    row[:, :, None, :].astype(c.dtype), (0, 0, pos, 0))
                kl, vl = put(kc, k), put(vc, v)
                qg = q.reshape(b, kl.shape[1], -1, dh)
                s = jnp.einsum("bhgd,bhkd->bhgk", qg, kl).astype(
                    jnp.float32) * scale
                s = jnp.where(jnp.arange(kl.shape[2]) <= pos, s, -1e9)
                w = jax.nn.softmax(s, axis=-1).astype(vl.dtype)
                att = jnp.einsum("bhgk,bhkd->bhgd", w, vl)
                return att.reshape(b, hq, dh), (_with_layer(kc, kl, layer),
                                                _with_layer(vc, vl, layer))
            return attend

        active = jnp.ones(tok.shape, bool)
        logits, (kc, vc), rec = self._tick(
            emb_p, runs_p, head_p, tok, pos, rec, active, (kc, vc),
            attend_at)
        return logits, kc, vc, rec

    def _step_paged(self, emb_p, runs_p, head_p, kc, vc, tok, pos, table,
                    wblk, woff, shard=None, kernel_writes=False, rec=None,
                    active=None):
        """The server's tick: the attention layers write and read the
        paged pool ``kc`` / ``vc`` [kv_layers, n_blocks, kv_heads,
        block_size, width] through ``table``, the Mamba layers advance
        ``rec`` for the ``active`` rows.  On the kernel route
        (``kernel_writes``) the pools are carried whole and touched by
        ``%paged_attention`` alone, as the post-LN tick's are.
        Returns (logits, kc, vc, rec)."""
        from deeplearning4j_tpu.kernels import (
            pad_head_dim, paged_decode_attention,
            paged_decode_write_attention, paged_route)
        scale = 1.0 / math.sqrt(self.head_dim)
        kernel = kernel_writes and paged_route(shard) == "pallas"

        def attend_at(kv, layer):
            def attend(q, k, v):
                kc, vc = kv
                if kernel:
                    att, kc, vc = paged_decode_write_attention(
                        q, k, v, kc, vc, table, pos, wblk, woff, layer,
                        scale=scale)
                    return att, (kc, vc)
                width = kc.shape[-1]
                put = lambda c, row: _layer_of(c, layer).at[
                    wblk, :, woff, :].set(pad_head_dim(row, width))
                kl, vl = put(kc, k), put(vc, v)
                att = paged_decode_attention(q, kl, vl, table, pos,
                                             scale=scale, shard=shard)
                return att, (_with_layer(kc, kl, layer),
                             _with_layer(vc, vl, layer))
            return attend

        logits, (kc, vc), rec = self._tick(
            emb_p, runs_p, head_p, tok, pos, rec, active, (kc, vc),
            attend_at)
        return logits, kc, vc, rec

    # -- prefill ---------------------------------------------------------
    def _prefill_rows(self, emb_p, runs_p, head_p, prompt, t0=None,
                      shard=None):
        """Whole-prompt forward.  Returns (logits [b, V] at position
        ``t0`` - 1, ks, vs [kv_layers, b, kv_heads, t, head_dim], rec):
        the K/V rows of the attention layers for the caller to place,
        and the recurrent state AS AFTER TOKEN ``t0`` -- the pad tail of
        a bucket does not advance it."""
        cd = self.compute_dtype
        x = _embed_prompt(self.emb, emb_p, prompt).astype(cd)
        ks, vs, hs, convs = [], [], [], []
        for run, p in zip(self.runs, runs_p):
            x, got = jax.lax.scan(
                lambda h, p_l, run=run: run.sequence(p_l, h, t0), x, p)
            if run.RECURRENT:
                hs.append(got["h"])
                convs.append(got["conv"].astype(cd))
            else:                # [n, b, t, h, dh] -> [n, b, h, t, dh]
                ks.append(got["k"].transpose(0, 1, 3, 2, 4).astype(cd))
                vs.append(got["v"].transpose(0, 1, 3, 2, 4).astype(cd))
        cat = lambda parts: (jnp.concatenate(parts, axis=0)
                             if len(parts) > 1 else parts[0])
        last = (x[:, -1] if t0 is None else
                jax.lax.dynamic_slice_in_dim(x, t0 - 1, 1, axis=1)[:, 0])
        rec = {"h": cat(hs), "conv": cat(convs)} if hs else None
        return (self._logits(emb_p, head_p, last), cat(ks), cat(vs), rec)

    def _generate_scan(self, emb_p, blk_ps, head_p, ids, rng_key, t0,
                       n_new, L, temperature, top_k=None, top_p=None):
        if self.compute_dtype != jnp.float32:
            emb_p, blk_ps, head_p = _cast_floating(
                (emb_p, blk_ps, head_p), self.compute_dtype)
        runs_p = self._stack_blocks(blk_ps)
        logits0, ks, vs, rec = self._prefill_rows(
            emb_p, runs_p, head_p, ids[:, :t0])
        pad = ((0, 0), (0, 0), (0, 0), (0, L - t0), (0, 0))
        kc, vc = jnp.pad(ks, pad), jnp.pad(vs, pad)

        def body(carry, pos):
            ids, kc, vc, rec, key, logits = carry
            nxt, key = _draw_token(logits, key, temperature, top_k, top_p)
            ids = jax.lax.dynamic_update_slice(ids, nxt[:, None], (0, pos))
            logits, kc, vc, rec = self._step(emb_p, runs_p, head_p, kc, vc,
                                             rec, nxt, pos)
            return (ids, kc, vc, rec, key, logits), None

        (ids, *_), _ = jax.lax.scan(
            body, (ids, kc, vc, rec, rng_key, logits0),
            t0 + jnp.arange(n_new))
        return ids
