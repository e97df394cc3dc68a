"""KV-cache incremental decoding — the transformer analogue of DL4J's
``rnnTimeStep`` (``MultiLayerNetwork.rnnTimeStep`` keeps per-layer
recurrent state between calls; here the state is each block's key/value
cache and, for a state-space block, its recurrent state).

TPU-first design: generation is ONE jitted ``lax.scan`` over time with
static shapes — the KV caches are preallocated [kv_layers, b, kv_heads,
max_len, head_dim] buffers, the prompt prefills in ONE batched causal
forward (matmul-rate, not the per-step params-bandwidth floor), and
sampling scans one token per tick — the whole decode is a single XLA
program, no per-token Python dispatch or retrace.

Nothing of a block is written here.  The stack between the embedding
and the head is a list of RUNS of identical blocks, the parameters of a
run stacked on a leading [layers] axis: prefill scans each run's
``sequence()`` and a decode tick scans each run's ``step()``, so the
program size is O(1) in a run's depth instead of inlining a copy of the
block body per layer.  What differs between the callers is how
attention reaches its keys, and that is handed to ``step()`` as
``attend``: a dense cache offline (``_step``), the paged pool in
``parallel/generation_server.py`` (``_step_paged``: many callers
multiplexed onto one decode tick, Orca-style continuous batching), the
W rows of a speculative verify (``_verify_rows_paged``).

Two stacks decode here:

* ``EmbeddingSequenceLayer -> N x TransformerEncoderBlock(causal=True)
  -> (Rnn)OutputLayer`` (e.g. ``zoo.Gpt``): the N conf-identical post-LN
  blocks are ONE run, their parameters stacked by ``_stack_blocks``;
* ``EmbeddingSequenceLayer -> runs of pre-norm Mamba / attention blocks
  -> TiedLMHead | LMHead`` (``nn/conf/layers_hybrid.py``; e.g.
  ``zoo.HybridDecoder``, ``zoo.SparseWindowDecoder``), stacked as the
  net holds them, so a snapshot of them is the tree itself.  Beside the
  K/V cache of its FULL-attention layers its decode carries per-row
  state of a fixed size, ``rec``, a dict of whichever of these the
  stack's runs keep (``None`` where none does):

      "h":      [rec_layers, b, d_state, d_inner] float32   (Mamba)
      "conv":   [rec_layers, b, d_conv - 1, d_inner] compute dtype
      "win_k":  [win_layers, b, kv_heads, window, qk_dim]   (a WINDOW
      "win_v":  [win_layers, b, kv_heads, window, v_dim]     run's ring:
                row j holds the newest position p with p % window == j)
      "routed": int32 [held + 1], the routed feed-forwards' tally: rows
                each held expert got, then every token-expert pair made
      "reached": int32 [1], the held experts that got a row, summed over
                the routed layers run (those whose weights were read)

  One pool a KIND of attention run, a kind being the run's
  ``(n_kv_heads, qk_dim, v_dim, window)``: the full-attention runs of a
  stack share one kind, its window runs another (their layers' rows
  need not be as many or as wide as the full kind's).

IMPORTED graphs (SameDiff IR) are NOT decodable here yet: they fine-tune
through ``fused_attention`` but have no cached-step form — a known gap
(the toy imported GPT is pre-LN with biases, so it maps onto neither
kind of block).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.nn.conf.layers_core import OutputLayer
from deeplearning4j_tpu.nn.conf.layers_hybrid import (AttentionBlockRun,
                                                      LMHead,
                                                      MambaBlockRun,
                                                      TiedLMHead)
from deeplearning4j_tpu.nn.conf.layers_transformer import (
    EmbeddingSequenceLayer, TransformerEncoderBlock, _layer_norm)


# Decode telemetry: tokens are THE serving unit for a causal decoder;
# steps/s is the per-row tick rate the params-bandwidth roofline
# bounds.  A generate() that retraces (new shape key)
# shows up as a latency outlier in generation_seconds, not a separate
# series — check _fn_cache hygiene when the histogram grows a tail.
_GEN_REQS = telemetry.counter(
    "generation_requests_total", "generate() calls")
_GEN_TOKENS = telemetry.counter(
    "generation_tokens_total", "new tokens emitted (rows x n_new)")
_GEN_RATE = telemetry.gauge(
    "generation_decode_steps_per_sec",
    "decode ticks/sec over the last generate() (per-row token rate)")
_GEN_TIME = telemetry.histogram(
    "generation_seconds",
    "wall time per generate() call incl. prefill, decode scan, host "
    "sync (first call per shape includes compile)")


def _embed_token(ly: EmbeddingSequenceLayer, params, tok, pos):
    """[b] int token -> [b, d].  ``pos`` is a scalar (one shared
    position, the offline decode scan) or a [b] int32 vector (per-row
    positions: the server's slots, a verify's flat rows).  Also [b, s]
    tokens at a [s] vector of positions (a prompt's uncached suffix)."""
    y = jnp.take(params["W"], tok.astype(jnp.int32), axis=0)
    if ly.add_positional:
        if jnp.ndim(pos) == 0:
            y = y + jax.lax.dynamic_slice_in_dim(
                params["P"], pos, 1, axis=0)[0]
        else:
            y = y + jnp.take(params["P"], pos, axis=0)
    if ly.layer_norm:
        y = _layer_norm(y, params["g"], params["b"], ly.eps)
    return y


def _embed_prompt(ly: EmbeddingSequenceLayer, params, ids):
    """[b, t0] int prompt -> [b, t0, d] (positions 0..t0-1)."""
    y = jnp.take(params["W"], ids.astype(jnp.int32), axis=0)
    if ly.add_positional:
        y = y + params["P"][: ids.shape[1]][None]
    if ly.layer_norm:
        y = _layer_norm(y, params["g"], params["b"], ly.eps)
    return y


def _filter_logits_rows(logits, top_k, top_p):
    """Per-row variant of ``_filter_logits`` for the generation
    server's vectorized sampler: ``top_k`` is a [b] int32 VECTOR (one
    k per slot; k == vocab disables filtering for that row — the
    minimum logit becomes the threshold and nothing is below it) and
    ``top_p`` is a [b] float32 VECTOR (one nucleus mass per slot;
    p >= 1 disables the cut for that row), so requests with different
    top-k/top-p settings ride one traced program."""
    V = logits.shape[-1]
    srt = jnp.sort(logits, axis=-1)              # ascending
    kth = jnp.take_along_axis(srt, (V - top_k)[:, None], axis=-1)
    logits = jnp.where(logits < kth, -jnp.inf, logits)
    srt_d = jnp.sort(logits, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(srt_d, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    # drop tokens whose preceding cumulative mass already covers p
    # (the top token always survives); the p >= 1 guard keeps "off"
    # rows EXACTLY unfiltered even when float cumsum rounds past 1
    cut = ((csum - probs) >= top_p[:, None]) & (top_p[:, None] < 1.0)
    srt_d = jnp.where(cut, jnp.inf, srt_d)
    thresh = jnp.min(srt_d, axis=-1, keepdims=True)
    return jnp.where(logits < thresh, -jnp.inf, logits)


def _filtered_logprobs_rows(logits, temp, top_k, top_p):
    """Log-probabilities of each row's ACTUAL sampling distribution:
    temperature-scale (rows with temp <= 0 are greedy — scaled by 1 so
    the row stays finite; callers mask them out), top-k/top-p filter
    via ``_filter_logits_rows``, then log-softmax (-inf survives for
    filtered-out tokens).  This is the density the speculative
    accept/residual math needs on BOTH sides of the rejection test —
    the draft's proposal distribution and the target's verify
    distribution must be the post-filter ones, or the committed stream
    drifts from what direct sampling would produce."""
    safe = jnp.where(temp > 0.0, temp, 1.0)
    lg = _filter_logits_rows(logits / safe[:, None], top_k, top_p)
    return jax.nn.log_softmax(lg, axis=-1)


def _filter_logits(logits, top_k, top_p):
    """Nucleus/top-k filtering on [b, V] logits (already
    temperature-scaled): outside-the-set entries go to -inf."""
    if top_k is not None:
        kth = jnp.sort(logits, axis=-1)[:, -int(top_k)][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        srt = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(srt, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        # drop tokens whose preceding cumulative mass already covers p
        # (the top token always survives)
        cut = (csum - probs) >= float(top_p)
        srt = jnp.where(cut, jnp.inf, srt)
        thresh = jnp.min(srt, axis=-1, keepdims=True)
        logits = jnp.where(logits < thresh, -jnp.inf, logits)
    return logits


def _cast_floating(tree, dtype):
    """Every floating leaf of ``tree`` in ``dtype`` (a leaf already in
    it is returned as it is: nothing is copied)."""
    return jax.tree_util.tree_map(
        lambda a: (a.astype(dtype)
                   if jnp.issubdtype(a.dtype, jnp.floating) else a), tree)


def _draw_token(logits, key, temperature, top_k, top_p):
    """The offline scan's next token [b] from ``logits`` [b, V]: the
    argmax, or at ``temperature`` > 0 a draw from the filtered
    distribution.  Returns (token, key)."""
    if temperature > 0.0:
        key, sub = jax.random.split(key)
        lg = _filter_logits(logits / temperature, top_k, top_p)
        nxt = jax.random.categorical(sub, lg, axis=-1)
    else:
        nxt = jnp.argmax(logits, axis=-1)
    return nxt.astype(jnp.int32), key




def _layer_of(stacked, layer):
    """Layer ``layer`` (a traced index) of a [layers, ...] array."""
    return jax.lax.dynamic_index_in_dim(stacked, layer, 0, keepdims=False)


def _with_layer(stacked, value, layer):
    return jax.lax.dynamic_update_index_in_dim(stacked, value, layer, 0)


def _scatter_then_read(kv, layer, wblk, woff, k, v, read):
    """XLA's write of the rows k / v into layer ``layer`` of the pools
    ``kv`` at (``wblk``, ``woff``), then ``read(k_layer, v_layer)`` of
    the written layer: where the kernel does not write the pool itself.
    The rows [..., h, dh] go in as the pool holds them
    (``kernels.paged_pool_rows``: a kernel-route pool's rows are whole
    lanes, several heads side by side where they fit).  Returns (what
    ``read`` gave, kv)."""
    from deeplearning4j_tpu.kernels import paged_pool_rows
    kc, vc = kv

    def put(c, rows):
        row = paged_pool_rows(rows[..., None, :], c.shape[2], c.shape[4])
        return _layer_of(c, layer).at[wblk, :, woff, :].set(row[..., 0, :])

    kl, vl = put(kc, k), put(vc, v)
    return read(kl, vl), (_with_layer(kc, kl, layer),
                          _with_layer(vc, vl, layer))


def _kind(run) -> tuple:
    """(n_kv_heads, qk_dim, v_dim, window) of an attention run: what
    two runs must share to share a pool."""
    dh = run.head_dim
    return (run.n_kv_heads, getattr(run, "qk_dim", None) or dh,
            getattr(run, "v_dim", None) or dh, getattr(run, "window", None))


def _windowed(run) -> bool:
    return not run.RECURRENT and _kind(run)[3] is not None


def _routed(run) -> bool:
    return getattr(run, "n_experts", None) is not None


def _reached(tally):
    """int32 [1]: of a routed layer's tally [held + 1] (or several
    layers' [n, held + 1]), the held experts that got a row."""
    return jnp.sum(tally[..., :-1] > 0).astype(jnp.int32)[None]


def _scan_split(run, p):
    """(the leaves of a run's stacked parameters a scan over its layers
    slices, the leaves it closes over whole): a routed run's expert
    matrices stay where they lie."""
    whole = run.whole_leaves(p) if hasattr(run, "whole_leaves") else {}
    return {k: v for k, v in p.items() if k not in whole}, whole


def _depth(run_p) -> int:
    """Layers in one run's stacked parameters (a truncated self-draft
    hands in fewer than the run was made with)."""
    return jax.tree_util.tree_leaves(run_p)[0].shape[0]


class TransformerGenerator:
    """Greedy / temperature / top-k / nucleus sampling with KV caches
    over a decoder MLN.  The prompt is prefilled in ONE batched causal
    forward (matmul-rate), then decode scans one token at a time.

    >>> gen = TransformerGenerator(net)
    >>> out = gen.generate(prompt_ids, n_new=64)      # [b, t0+64]
    >>> out = gen.generate(prompt_ids, n_new=64, temperature=0.8,
    ...                    top_k=40, top_p=0.95)
    """

    def __init__(self, net, compute_dtype: Optional[str] = None):
        layers = list(net.layers)
        if not isinstance(layers[0], EmbeddingSequenceLayer):
            raise ValueError("generator expects EmbeddingSequenceLayer "
                             f"first, got {type(layers[0]).__name__}")
        self.net, self.emb, self.head = net, layers[0], layers[-1]
        self.blocks = layers[1:-1]
        # runs of pre-norm blocks are made stacked ([n_blocks, ...])
        self._made_stacked = bool(self.blocks) and all(
            isinstance(l, (MambaBlockRun, AttentionBlockRun))
            for l in self.blocks)
        if self._made_stacked:
            if not isinstance(self.head, TiedLMHead):
                raise ValueError(
                    "a stack of block runs decodes through a TiedLMHead "
                    f"or an LMHead, got {type(self.head).__name__}")
            self.runs = self.blocks
            self._layers = [r.n_blocks for r in self.runs]
        else:
            self.runs = [self._one_post_ln_run()]
            self._layers = [len(self.blocks)]
        attn = [r for r in self.runs if not r.RECURRENT]
        rec = [r for r in self.runs if r.RECURRENT]
        full = [r for r in attn if not _windowed(r)]
        win = [r for r in attn if _windowed(r)]
        # one pool and one stacked state serve every run of a kind
        for what, runs in (("full-attention", full), ("window", win)):
            if len({_kind(r) for r in runs}) > 1:
                raise ValueError(
                    f"the {what} runs of one stack share one K/V pool: "
                    "n_kv_heads, qk_dim, v_dim and window must agree (a "
                    "stack has one pool a kind -- full, window -- and "
                    "each kind's runs are of one shape)")
        if len({(r.d_state, r.d_inner, r.d_conv) for r in rec}) > 1:
            raise ValueError("the recurrent runs of one stack share one "
                             "state: d_state, d_inner and d_conv must agree")
        if len({r.held_experts[1] for r in attn if _routed(r)}) > 1:
            raise ValueError("the routed runs of one stack share one "
                             "tally: they must hold as many experts each")
        if not full:
            raise ValueError("a stack without a full-attention run has no "
                             "K/V pool to page (not supported)")
        self._attn, self._rec = full[0], (rec[0] if rec else None)
        self._win = win[0] if win else None
        self._held = next((r.held_experts[1] for r in attn if _routed(r)),
                          None)
        #: why a server cannot share, restore, re-verify or shard this
        #: stack's K/V rows (the run kind's ``REFUSES``; None: it can)
        self.refuses = next((r.REFUSES for r in rec + attn if r.REFUSES),
                            None)
        self.compute_dtype = (jnp.dtype(compute_dtype)
                              if compute_dtype else jnp.float32)
        self._fn_cache = {}

    def _one_post_ln_run(self):
        """The conf of a stack of conf-identical causal post-LN blocks
        under an (Rnn)OutputLayer: stacked, they are one run."""
        if not all(isinstance(l, TransformerEncoderBlock)
                   for l in self.blocks):
            raise ValueError("generator expects a pure "
                             "TransformerEncoderBlock stack")
        if not all(l.causal for l in self.blocks):
            raise ValueError("generation requires causal=True blocks")
        ref = dataclasses.asdict(self.blocks[0])
        if any(dataclasses.asdict(l) != ref for l in self.blocks[1:]):
            # a run's parameters are stacked on a leading axis and
            # lax.scan'ed: that needs conf-identical blocks.  Every
            # in-tree decoder (zoo.Gpt) is homogeneous.
            raise ValueError("generator requires conf-identical "
                             "TransformerEncoderBlocks (the decode "
                             "tick scans stacked block params)")
        if not isinstance(self.head, OutputLayer):
            # RnnOutputLayer subclasses OutputLayer: any W/b softmax
            # head over the final hidden state decodes
            raise ValueError("generator expects an (Rnn)OutputLayer "
                             f"head, got {type(self.head).__name__}")
        return self.blocks[0]

    # -- what a server sizes its pools and state by --------------------
    # the FULL kind's (the paged, allocated pool) ...
    kv_layers = property(lambda self: sum(
        n for r, n in zip(self.runs, self._layers)
        if not r.RECURRENT and not _windowed(r)))
    kv_heads = property(lambda self: self._attn.n_kv_heads)
    qk_dim = property(lambda self: _kind(self._attn)[1])
    v_dim = property(lambda self: _kind(self._attn)[2])
    head_dim = qk_dim
    # ... the WINDOW kind's (None: the stack has no window run) ...
    window_kind = property(
        lambda self: None if self._win is None else _kind(self._win))
    window_sink = property(
        lambda self: bool(getattr(self._win, "sink", False)))
    win_layers = property(lambda self: sum(
        n for r, n in zip(self.runs, self._layers) if _windowed(r)))
    # ... and the routed runs' layers and the experts each holds
    routed_layers = property(lambda self: sum(
        n for r, n in zip(self.runs, self._layers) if _routed(r)))
    held_experts = property(lambda self: self._held)
    vocab_size = property(lambda self: int(self.head.n_out))

    def window_blocks(self, block_size: int) -> int:
        """Blocks of ``block_size`` positions a slot's window takes."""
        return -(-self._win.window // block_size)

    def fresh_rec(self, b: int, block_size: Optional[int] = None,
                  shard=None):
        """The per-row state of ``b`` rows that have seen nothing (None:
        the stack keeps none).  With ``block_size`` the window rings are
        a server's: paged pools [win_layers, b * window_blocks + 1,
        *kernels.paged_pool_shape], block 0 the scratch sink,
        row b's ring in blocks ``1 + b * window_blocks ..``, for life."""
        from deeplearning4j_tpu.kernels import paged_pool_shape
        out, r = {}, self._rec
        if r is not None:
            layers = (sum(self._layers) - self.kv_layers
                      - self.win_layers)
            out["h"] = jnp.zeros((layers, b, r.d_state, r.d_inner),
                                 jnp.float32)
            out["conv"] = jnp.zeros((layers, b, r.d_conv - 1, r.d_inner),
                                    self.compute_dtype)
        if self._win is not None:
            hkv, dk, dv, window = _kind(self._win)
            if block_size is None:
                tails = ((b, hkv, window, dk), (b, hkv, window, dv))
            else:
                tails = ((b * self.window_blocks(block_size) + 1,) + tail
                         for tail in paged_pool_shape(hkv, block_size, dk,
                                                      dv, shard))
            for name, tail in zip(("win_k", "win_v"), tails):
                out[name] = jnp.zeros((self.win_layers,) + tail,
                                      self.compute_dtype)
        if self._held is not None:
            out["routed"] = jnp.zeros((self._held + 1,), jnp.int32)
            out["reached"] = jnp.zeros((1,), jnp.int32)
        return out or None

    def _params(self):
        self.net._check_init()   # fires any lazy _param_sync_hook
        pt = self.net.params_tree
        n = len(self.net.layers)
        return (pt["layer_0"],
                [pt[f"layer_{i}"] for i in range(1, n - 1)],
                pt[f"layer_{n - 1}"])

    def _stack_blocks(self, blk_ps):
        """The middle layers' param dicts -> one dict a RUN, every leaf
        with a leading [layers] axis: what the scans over a run consume.
        Runs made stacked are the net's own arrays (a snapshot copies
        nothing); N post-LN blocks are stacked here (inside jit a
        compile-time concatenate)."""
        if self._made_stacked:
            return tuple(blk_ps)
        return (jax.tree_util.tree_map(lambda *ls: jnp.stack(ls),
                                       *blk_ps),)

    def _logits(self, emb_p, head_p, x, shard=None):
        """float32 logits of the final hidden rows x [..., d]; under a
        mesh they gather, so a sampler's argmax / sort runs on the full
        vocabulary row."""
        if isinstance(self.head, TiedLMHead):
            table = head_p if isinstance(self.head, LMHead) else emb_p
            logits = self.head.logits(head_p, table["W"], x)
        else:
            logits = x.astype(jnp.float32) @ head_p["W"] + head_p["b"]
        return logits if shard is None else shard.rep(logits)

    # -- one tick --------------------------------------------------------
    def _tick(self, emb_p, runs_p, head_p, tok, pos, rec, active, kv,
              attend_at, shard=None, attend_win=None):
        """Every run's ``step()`` in turn over the rows ``tok`` at
        ``pos``.  ``kv`` is the full-attention layers' cache, carried
        WHOLE through each run's layer scan (xs: the run's stacked
        parameters and the layer's index) and threaded through
        ``attend_at(kv, layer)(q, k, v) -> (att, kv)``; ``rec`` the rest
        of the per-row state: the recurrent layers', advanced for the
        ``active`` rows; the window runs' rings, threaded likewise
        through ``attend_win((win_k, win_v), layer)``; the routed runs'
        tally, which the ``active`` rows add to.
        Returns (logits, kv, rec)."""
        x = _embed_token(self.emb, emb_p, tok, pos).astype(
            self.compute_dtype)
        if shard is not None:
            x = shard.rep(x)
        rec = dict(rec or {})
        at = {"kv": 0, "win": 0, "rec": 0}
        caches = {"kv": kv, "win": (rec.get("win_k"), rec.get("win_v"))}
        for run, p in zip(self.runs, runs_p):
            n = _depth(p)
            if run.RECURRENT:
                def body(carry, xs, run=run):
                    h, state = carry
                    return run.step(xs[0], h, state, xs[1], active), None
                (x, state), _ = jax.lax.scan(
                    body, (x, {k: rec[k] for k in ("h", "conv")}),
                    (p, jnp.arange(at["rec"], at["rec"] + n)))
                rec.update(state)
                at["rec"] += n
                continue
            which = "win" if _windowed(run) else "kv"
            attend = attend_win if which == "win" else attend_at
            # a run of layers_hybrid rotates at pos and routes the live
            # rows; a post-LN block knows neither
            extra = ({"pos": pos, "live": active}
                     if isinstance(run, AttentionBlockRun) else {})
            tally = ((rec["routed"], rec["reached"]) if _routed(run)
                     else ())
            sliced, whole = _scan_split(run, p)

            def body(carry, xs, run=run, attend=attend, extra=extra,
                     whole=whole, first=at[which]):
                h, cache, *tally = carry
                if whole:
                    extra = {**extra, "layer": xs[1] - first}
                h, cache, *new = run.step(
                    {**xs[0], **whole}, h, attend(cache, xs[1]),
                    shard=shard, **extra)
                if new:
                    new = (new[0], _reached(new[0]))
                return (h, cache, *(t + d for t, d in zip(tally, new))), None
            (x, caches[which], *tally), _ = jax.lax.scan(
                body, (x, caches[which], *tally),
                (sliced, jnp.arange(at[which], at[which] + n)))
            if tally:
                rec["routed"], rec["reached"] = tally
            at[which] += n
        if self._win is not None:
            rec["win_k"], rec["win_v"] = caches["win"]
        return (self._logits(emb_p, head_p, x, shard), caches["kv"],
                rec or None)

    def _step(self, emb_p, runs_p, head_p, kc, vc, rec, tok, pos):
        """One offline decode tick over dense caches ``kc`` / ``vc``
        [kv_layers, b, kv_heads, L, qk_dim | v_dim]: writes position
        ``pos`` (a scalar), attends over <= pos.  float32 scores, a
        -1e9 mask, softmax in float32: ``sequence()``'s math, which is
        what makes cached decode equal the full forward.  A window run
        writes its ring (``rec["win_k"]`` / ``["win_v"]``) at ``pos %
        window`` and attends over the rows written so far.  Returns
        (logits [b, V], kc, vc, rec)."""
        from deeplearning4j_tpu.kernels import softmax_with_sink

        def dense(run, at, n_seen):
            """``attend_at`` over a pair of dense caches, the row put at
            column ``at``, columns < ``n_seen`` read."""
            scale = 1.0 / math.sqrt(_kind(run)[1])

            def attend_at(kv, layer):
                def attend(q, k, v, sink=None):
                    b, hq, dh = q.shape
                    kc, vc = kv
                    put = lambda c, row: jax.lax.dynamic_update_slice(
                        _layer_of(c, layer),
                        row[:, :, None, :].astype(c.dtype), (0, 0, at, 0))
                    kl, vl = put(kc, k), put(vc, v)
                    # grouped query heads: hq // kv_heads on each K/V head
                    qg = q.reshape(b, kl.shape[1], -1, dh)
                    s = jnp.einsum("bhgd,bhkd->bhgk", qg, kl).astype(
                        jnp.float32) * scale
                    s = jnp.where(jnp.arange(kl.shape[2]) < n_seen, s, -1e9)
                    w = softmax_with_sink(s, sink).astype(vl.dtype)
                    att = jnp.einsum("bhgk,bhkd->bhgd", w, vl)
                    return att.reshape(b, hq, -1), (
                        _with_layer(kc, kl, layer),
                        _with_layer(vc, vl, layer))
                return attend
            return attend_at

        win = self._win
        active = jnp.ones(tok.shape, bool)
        logits, (kc, vc), rec = self._tick(
            emb_p, runs_p, head_p, tok, pos, rec, active, (kc, vc),
            dense(self._attn, pos, pos + 1),
            attend_win=None if win is None else dense(
                win, pos % win.window, pos + 1))
        return logits, kc, vc, rec

    def _step_paged(self, emb_p, runs_p, head_p, kc, vc, tok, pos, table,
                    wblk, woff, shard=None, kernel_writes=False, rec=None,
                    active=None):
        """The server's tick: ``kc`` / ``vc`` are the global block pools
        [kv_layers, n_blocks, kv_heads, block_size, width], ``table``
        [b, max_blocks] the per-slot block tables, ``pos`` [b]; the new
        K/V row of a slot lands at (``wblk``, ``woff``) -- the caller
        masks inactive slots to the scratch block 0 -- and attention
        reads THROUGH the table (``kernels.paged_decode_attention``; its
        reference path mirrors the dense cache's math exactly, which is
        what byte parity with offline decode rests on).  The recurrent
        layers advance ``rec`` for the ``active`` rows.

        ``kernel_writes`` (the server's decode scan) lets the kernel
        route have the pools written and read by ``%paged_attention``
        alone (``kernels.paged_decode_write_attention``): an XLA scatter
        of single rows makes XLA hold the loop's pool token-major inside
        a block, and the kernel's row-major operands then cost a slice,
        a layout copy and a write-back of a layer's pool per layer per
        tick (79% of the device's time before PR 26).  Otherwise the
        layer's pool is sliced, scattered into and put back.  A
        kernel-route pool's rows are whole 128-lane rows
        (``kernels.paged_pool_shape``): as many heads side by side as
        fit, zero past the last.

        ``shard`` (TpShardCtx) makes this the mesh-sharded tick: embeds
        replicate, block math shards heads / columns along ``tp`` with
        explicit replication before feature reductions, the logits
        gather -- byte-identical to the unsharded program by
        construction.

        A WINDOW run's ring is paged too (``rec["win_k"]`` /
        ``["win_v"]``, ``fresh_rec(b, block_size)``'s layout): slot b's
        table is its own blocks, for life; the row lands at ring
        position ``pos % window`` and the read covers the ``min(pos + 1,
        window)`` rows written -- keys are cached rotated and softmax
        does not mind the order, so it is the SAME call as a full
        layer's, through a table one window long (``window_blocks``
        entries: the kernel route is told which of them the row lands
        in, where that is not the last one read).
        Returns (logits, kc, vc, rec)."""
        from deeplearning4j_tpu.kernels import (
            paged_decode_attention, paged_decode_write_attention,
            paged_route)
        kernel = kernel_writes and paged_route(shard) == "pallas"

        def paged(run, table, pos, wblk, woff, write_at=None):
            scale = 1.0 / math.sqrt(_kind(run)[1])

            def attend_at(kv, layer):
                def attend(q, k, v, sink=None):
                    if kernel:
                        att, kc, vc = paged_decode_write_attention(
                            q, k, v, *kv, table, pos, wblk, woff, layer,
                            scale=scale, sink=sink, write_at=write_at)
                        return att, (kc, vc)
                    return _scatter_then_read(
                        kv, layer, wblk, woff, k, v,
                        lambda kl, vl: paged_decode_attention(
                            q, kl, vl, table, pos, scale=scale,
                            shard=shard, sink=sink, kv_heads=k.shape[1]))
                return attend
            return attend_at

        attend_win = None
        if self._win is not None:
            window, bs = self._win.window, rec["win_k"].shape[3]
            wb = self.window_blocks(bs)
            mine = 1 + jnp.arange(tok.shape[0], dtype=jnp.int32) * wb
            ring = pos % window
            attend_win = paged(
                self._win, mine[:, None] + jnp.arange(wb, dtype=jnp.int32),
                jnp.minimum(pos, window - 1),
                jnp.where(wblk != 0, mine + ring // bs, 0),
                jnp.where(wblk != 0, ring % bs, 0),
                # a ring over several blocks is written on anywhere
                ring // bs if wb > 1 else None)
        logits, (kc, vc), rec = self._tick(
            emb_p, runs_p, head_p, tok, pos, rec, active, (kc, vc),
            paged(self._attn, table, pos, wblk, woff), shard, attend_win)
        return logits, kc, vc, rec

    def _verify_rows_paged(self, emb_p, runs_p, head_p, kc, vc, toks,
                           pos0, epos, table, wblk, woff, shard=None):
        """Speculative verification forward: ONE batched pass over a
        chunk of W tokens per slot — ``toks`` [B, W] (the anchor + the
        draft's proposals, inactive rows masked to 0), ``pos0`` [B]
        the chunk's base position per slot, ``epos`` [B, W] the embed
        positions (masked rows clamped to 0 so the positional take
        never reads out of bounds — the PR 2 NaN class), ``wblk`` /
        ``woff`` [B, W] the per-token write targets through the
        slot's block table (masked rows at the scratch block 0).

        Returns (logits [B, W, V], kc, vc, rec = None): logits at EVERY
        chunk position — G_j is the target's distribution after
        consuming tokens 0..j, which is both the acceptance judge and
        the held logits the round hands forward.  The tick runs over
        FLAT [B*W] rows -- every matmul and layer norm of ``step()`` at
        the 2-D shapes that are row-bitwise-stable on the backends --
        and attention unrolls per query row, row j at ``pos0 + j``
        (``kernels.paged_verify_attention``): logits AND cache writes
        are bitwise equal to W sequential ``_step_paged`` ticks, the
        invariant speculative greedy parity rests on."""
        from deeplearning4j_tpu.kernels import paged_verify_attention
        B, W = toks.shape
        scale = 1.0 / math.sqrt(self.qk_dim)
        chunk = lambda z: z.reshape((B, W) + z.shape[1:])

        def attend_at(kv, layer):
            def attend(q, k, v):
                return _scatter_then_read(
                    kv, layer, wblk, woff, chunk(k), chunk(v),
                    lambda kl, vl: paged_verify_attention(
                        chunk(q), kl, vl, table, pos0, scale=scale,
                        shard=shard))
            return attend

        logits, (kc, vc), rec = self._tick(
            emb_p, runs_p, head_p, toks.reshape(B * W),
            epos.reshape(B * W), None, None, (kc, vc), attend_at, shard)
        return logits.reshape(B, W, -1), kc, vc, rec

    # -- prefill ---------------------------------------------------------
    def _sequences(self, emb_p, runs_p, head_p, x, t0, last_ix,
                   prefix=None, shard=None):
        """Every run's ``sequence()`` in turn over the embedded rows x
        [b, t, d].  Returns (logits [b, V] at row ``last_ix`` (default:
        the last), ks, vs [kv_layers, b, kv_heads, t, qk_dim | v_dim] of
        the full-attention layers, rec: the rest AS AFTER TOKEN ``t0``
        -- the recurrent state, the window runs' rings, the routed
        runs' tally over the real positions)."""
        cd = self.compute_dtype
        x = x.astype(cd)
        if shard is not None:
            x = shard.rep(x)
        ks, vs, hs, convs, wks, wvs, tally, reached = ([] for _ in range(8))
        kv_l = 0
        for run, p in zip(self.runs, runs_p):
            if run.RECURRENT:
                x, got = jax.lax.scan(
                    lambda h, p_l, run=run: run.sequence(p_l, h, t0), x, p)
                hs.append(got["h"])
                convs.append(got["conv"].astype(cd))
                continue
            n = _depth(p)
            sliced, whole = _scan_split(run, p)
            # a routed run's experts are read where they lie
            at_layer = lambda l, whole=whole: {"layer": l} if whole else {}
            if prefix is None:
                x, got = jax.lax.scan(
                    lambda h, xs, run=run, whole=whole: run.sequence(
                        {**xs[0], **whole}, h, t0, shard=shard,
                        **at_layer(xs[1])), x, (sliced, jnp.arange(n)))
            else:
                pk, pv, p0 = prefix
                mine = slice(kv_l, kv_l + n)
                x, got = jax.lax.scan(
                    lambda h, xs, run=run: run.sequence(
                        xs[0], h, t0, prefix=(xs[1], xs[2], p0),
                        shard=shard), x, (p, pk[mine], pv[mine]))
            if "routed" in got:
                tally.append(jnp.sum(got["routed"], axis=0))
                reached.append(_reached(got["routed"]))
            if _windowed(run):          # its ring as after token t0
                wks.append(got["k"].astype(cd))
                wvs.append(got["v"].astype(cd))
                continue
            ks.append(got["k"].astype(cd))
            vs.append(got["v"].astype(cd))
            kv_l += n
        cat = lambda parts: (jnp.concatenate(parts, axis=0)
                             if len(parts) > 1 else parts[0])
        last = (x[:, -1] if last_ix is None else
                jax.lax.dynamic_slice_in_dim(x, last_ix, 1, axis=1)[:, 0])
        rec = {"h": cat(hs), "conv": cat(convs)} if hs else {}
        if wks:
            rec.update(win_k=cat(wks), win_v=cat(wvs))
        if tally:
            rec["routed"], rec["reached"] = sum(tally), sum(reached)
        return (self._logits(emb_p, head_p, last, shard), cat(ks), cat(vs),
                rec or None)

    def _prefill_rows(self, emb_p, runs_p, head_p, prompt, t0=None,
                      shard=None):
        """Whole-prompt forward, one batched pass a run.  Returns
        (logits [b, V], ks, vs [kv_layers, b, kv_heads, t, head_dim],
        rec): the raw K/V rows for the caller to place (offline decode
        zero-pads to L; the server scatters into a slot's blocks), and
        the recurrent state AS AFTER TOKEN ``t0`` -- the pad tail of a
        bucket does not advance it.  ``t0`` picks the logits position of
        a prompt PADDED past its real length (causal masking makes
        position t0-1 independent of the pad tail); default is the last
        column.  THE prefill numerics both decode paths share:
        byte-identical greedy parity between them depends on exactly
        this."""
        return self._sequences(
            emb_p, runs_p, head_p, _embed_prompt(self.emb, emb_p, prompt),
            t0, None if t0 is None else t0 - 1, shard=shard)

    def _prefill_rows_chunked(self, emb_p, runs_p, head_p, suffix, pk, pv,
                              p0, last_ix, shard=None):
        """``_prefill_rows`` for prefix-cache HITS: ``suffix`` [b, s]
        are the uncached prompt tokens at global positions p0..p0+s-1
        (pad tail beyond the real suffix), ``pk`` / ``pv`` [kv_layers,
        b, kv_heads, P, head_dim] the cached prefix K/V gathered out of
        the block pool (valid cols < p0).  Logits at local row
        ``last_ix`` = t0-p0-1; ks / vs are the SUFFIX rows only, for the
        caller to scatter into fresh blocks.  Prefill runs only on the
        suffix: the prefix's compute is the work the cache saves.  The
        positional take clamps the pad tail (finite garbage, masked
        before any read)."""
        x = _embed_token(self.emb, emb_p, suffix,
                         p0 + jnp.arange(suffix.shape[1]))
        return self._sequences(emb_p, runs_p, head_p, x, None, last_ix,
                               prefix=(pk, pv, p0), shard=shard)

    def generate(self, prompt_ids, n_new: int, temperature: float = 0.0,
                 seed: int = 0, max_len: Optional[int] = None,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None):
        """[b, t0] int prompt -> [b, t0 + n_new].  temperature == 0 is
        greedy argmax; > 0 samples logits/temperature, optionally
        filtered to the top-k tokens and/or the top-p nucleus."""
        prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
        b, t0 = prompt_ids.shape
        total = t0 + n_new
        L = max_len or total
        if L < total:
            raise ValueError(f"max_len {L} < prompt+new {total}")
        if self.emb.add_positional and L > self.emb.max_len:
            # past the table, dynamic_slice would silently clamp and
            # every later position would reuse the LAST positional row
            raise ValueError(
                f"generation length {L} exceeds the model's positional "
                f"table ({self.emb.max_len} rows); re-configure "
                "EmbeddingSequenceLayer.max_len or shorten the request")
        if (top_k is not None or top_p is not None) and temperature <= 0:
            raise ValueError("top_k/top_p need temperature > 0 "
                             "(greedy ignores the filtered tail)")
        if top_k is not None:
            # ADVICE r5: JAX clamps out-of-range sort indices, so
            # top_k=0 / top_k>vocab would SILENTLY disable filtering
            # (kth becomes the min logit); top_k is static per jit key,
            # so a plain Python check catches it here.
            vocab = self.vocab_size
            if not 1 <= int(top_k) <= vocab:
                raise ValueError(
                    f"top_k={top_k} out of range [1, {vocab}] "
                    "(vocab size)")
        key = (b, t0, n_new, L, float(temperature), top_k,
               None if top_p is None else float(top_p))
        if key not in self._fn_cache:
            self._fn_cache[key] = jax.jit(
                lambda e, bl, h, ids, k: self._generate_scan(
                    e, bl, h, ids, k, t0, n_new, L, temperature,
                    top_k, top_p))
        emb_p, blk_ps, head_p = self._params()
        ids = jnp.concatenate(
            [prompt_ids, jnp.zeros((b, n_new), jnp.int32)], axis=1)
        t_start = time.perf_counter()
        with telemetry.span("generate", batch=b, prompt=t0, new=n_new):
            out = np.asarray(self._fn_cache[key](
                emb_p, blk_ps, head_p, ids, jax.random.PRNGKey(seed)))
        dt = time.perf_counter() - t_start
        _GEN_REQS.inc()
        _GEN_TOKENS.inc(b * n_new)
        _GEN_TIME.observe(dt)
        if dt > 0:
            _GEN_RATE.set(n_new / dt)
        return out


    def _generate_scan(self, emb_p, blk_ps, head_p, ids, rng_key, t0,
                       n_new, L, temperature, top_k=None, top_p=None):
        if self.compute_dtype != jnp.float32:
            # cast the full parameter set ONCE inside the program: the
            # decode scan re-reads every parameter each tick, and
            # streaming f32-stored weights costs 2x the bytes of the
            # bf16 math actually performed
            emb_p, blk_ps, head_p = _cast_floating(
                (emb_p, blk_ps, head_p), self.compute_dtype)
        runs_p = self._stack_blocks(blk_ps)
        logits0, ks, vs, rec = self._prefill_rows(
            emb_p, runs_p, head_p, ids[:, :t0])
        pad = ((0, 0), (0, 0), (0, 0), (0, L - t0), (0, 0))
        kc, vc = jnp.pad(ks, pad), jnp.pad(vs, pad)

        def body(carry, pos):
            # sample the token AT pos from the previous logits, write
            # it, embed it, advance the caches
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
