"""KV-cache incremental decoding — the transformer analogue of DL4J's
``rnnTimeStep`` (``MultiLayerNetwork.rnnTimeStep`` keeps per-layer
recurrent state between calls; here the state is each block's key/value
cache).

TPU-first design: generation is ONE jitted ``lax.scan`` over time with
static shapes — the KV caches are preallocated [n_layers, b, h,
max_len, dh] buffers written via ``lax.dynamic_update_slice``, the
prompt prefills in ONE batched causal forward (matmul-rate, not the
per-step params-bandwidth floor), and sampling scans one token per
tick — the whole decode is a single XLA program, no per-token Python
dispatch or retrace.  The homogeneous block params are stacked on a
leading [n_layers] axis and BOTH the prefill and the decode tick
``lax.scan`` over layers, so the program size is O(1) in depth instead
of inlining n_layers copies of the block body.

Concurrent serving over this machinery (many callers multiplexed onto
one decode tick, Orca-style continuous batching) lives in
``parallel/generation_server.py`` — ``_embed_token``/
``_block_decode_step`` accept per-row position VECTORS for exactly
that caller.

Works over any MultiLayerNetwork whose stack is
``EmbeddingSequenceLayer -> N x TransformerEncoderBlock(causal=True)
-> (Rnn)OutputLayer`` (e.g. ``zoo.Gpt``), and -- through
``generation_runs.RunsGenerator``, which ``TransformerGenerator(net)``
returns for it -- over ``EmbeddingSequenceLayer -> runs of pre-norm
Mamba / attention blocks -> TiedLMHead`` (``zoo.HybridDecoder``), whose
decode also carries a recurrent state per row.  IMPORTED graphs (SameDiff
IR) are NOT decodable here yet: they fine-tune through
``fused_attention`` but have no cached-step form — a known gap (the
toy imported GPT is pre-LN, so it cannot be mapped onto the post-LN
zoo blocks either).
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.nn.conf.layers_core import OutputLayer
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
    positions, the continuous-batching server's slots)."""
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


def _block_decode_step(ly: TransformerEncoderBlock, params, kcache,
                       vcache, x, pos):
    """One cached decoder step.  x: [b, d] new-token hidden; caches
    [b, h, L, dh]; writes position ``pos``, attends over <= pos.
    ``pos`` may be a scalar (whole batch at one position) or a [b]
    vector (per-row positions — slots in the generation server decode
    at independent depths inside ONE static-shape program).
    Returns (y [b, d], kcache, vcache)."""
    b, d = x.shape
    h, dh = ly.n_heads, d // ly.n_heads
    L = kcache.shape[2]
    cast = lambda w: w.astype(x.dtype)

    qkv = x @ cast(params["Wqkv"]) + cast(params["bqkv"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    split = lambda z: z.reshape(b, h, 1, dh)
    q, k, v = split(q), split(k), split(v)
    if jnp.ndim(pos) == 0:
        kcache = jax.lax.dynamic_update_slice(kcache, k, (0, 0, pos, 0))
        vcache = jax.lax.dynamic_update_slice(vcache, v, (0, 0, pos, 0))
        valid = (jnp.arange(L) <= pos)[None, None, None, :]
    else:
        write = jax.vmap(lambda c, n, p: jax.lax.dynamic_update_slice(
            c, n, (0, p, 0)))
        kcache = write(kcache, k, pos)
        vcache = write(vcache, v, pos)
        valid = (jnp.arange(L)[None, :]
                 <= pos[:, None])[:, None, None, :]

    scale = 1.0 / (dh ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kcache).astype(jnp.float32)
    s = s * scale
    s = jnp.where(valid, s, -1e9)
    p = jax.nn.softmax(s, axis=-1).astype(vcache.dtype)
    att = jnp.einsum("bhqk,bhkd->bhqd", p, vcache)
    att = att.transpose(0, 2, 1, 3).reshape(b, d)
    att = att @ cast(params["Wo"]) + cast(params["bo"])
    hdn = _layer_norm(x + att, params["ln1_g"], params["ln1_b"], ly.eps)

    from deeplearning4j_tpu.nn.activations import get_activation
    act = get_activation(ly.activation or "gelu")
    ffn = act(hdn @ cast(params["W1"]) + cast(params["b1"]))
    ffn = ffn @ cast(params["W2"]) + cast(params["b2"])
    y = _layer_norm(hdn + ffn, params["ln2_g"], params["ln2_b"], ly.eps)
    return y, kcache, vcache


def _block_decode_step_paged(ly: TransformerEncoderBlock, params,
                             kpool, vpool, x, pos, table, wblk, woff,
                             shard=None, layer=None):
    """Paged-cache variant of ``_block_decode_step``: the slot's K/V
    live in pool blocks routed by a block table instead of a
    contiguous stripe.  x: [b, d] new-token hidden; ``kpool``/``vpool``
    [n_blocks, h, block_size, dh]; ``table`` [b, max_blocks] int32;
    the new K/V row lands at (``wblk``, ``woff``) per slot — the
    caller masks inactive slots to the scratch block 0 — and attention
    reads THROUGH the table (``kernels.paged_decode_attention``; the
    reference path mirrors the stripe step's f32-score/-1e9-mask math
    exactly, which is what byte parity with offline decode rests on).

    ``shard`` (a ``parallel.mesh.TpShardCtx``, or None = identity) is
    the mesh-sharded tick's parity contract: weights arrive with their
    OUTPUT columns sharded along ``tp`` (heads ride along when qkv
    splits), and ``shard.rep`` gathers the feature axis back to full
    replication at EXACTLY the points where the math reduces over it —
    before ``@ Wo``, both layer norms, and ``@ W2`` — so no device
    ever sums a partial feature axis.

    ``layer`` (a traced index, kernel route only) says the pools are
    the WHOLE [n_layers, n_blocks, h, block_size, width] ones: the
    kernel then writes the row itself and XLA never touches the pool
    (``kernels.paged_decode_write_attention``).  A kernel-route pool's
    rows are ``kernels.paged_pool_width(dh)`` wide, zero past ``dh``.
    Returns (y [b, d], kpool, vpool)."""
    from deeplearning4j_tpu.kernels import (pad_head_dim,
                                            paged_decode_attention,
                                            paged_decode_write_attention)
    rep = shard.rep if shard is not None else (lambda t: t)
    b, d = x.shape
    h, dh = ly.n_heads, d // ly.n_heads
    cast = lambda w: w.astype(x.dtype)

    qkv = x @ cast(params["Wqkv"]) + cast(params["bqkv"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    split = lambda z: z.reshape(b, h, dh)
    q, k, v = split(q), split(k), split(v)
    if layer is None:
        width = kpool.shape[-1]     # past dh on the kernel route
        kpool = kpool.at[wblk, :, woff, :].set(pad_head_dim(k, width))
        vpool = vpool.at[wblk, :, woff, :].set(pad_head_dim(v, width))
        att = paged_decode_attention(q, kpool, vpool, table, pos,
                                     scale=1.0 / (dh ** 0.5), shard=shard)
    else:
        att, kpool, vpool = paged_decode_write_attention(
            q, k, v, kpool, vpool, table, pos, wblk, woff, layer,
            scale=1.0 / (dh ** 0.5))
    att = rep(att.reshape(b, d))
    att = att @ cast(params["Wo"]) + cast(params["bo"])
    hdn = _layer_norm(rep(x + att), params["ln1_g"], params["ln1_b"],
                      ly.eps)

    from deeplearning4j_tpu.nn.activations import get_activation
    act = get_activation(ly.activation or "gelu")
    ffn = act(hdn @ cast(params["W1"]) + cast(params["b1"]))
    ffn = rep(ffn) @ cast(params["W2"]) + cast(params["b2"])
    y = _layer_norm(rep(hdn + ffn), params["ln2_g"], params["ln2_b"],
                    ly.eps)
    return y, kpool, vpool


def _block_verify_step_paged(ly: TransformerEncoderBlock, params,
                             kpool, vpool, x, table, wblk, woff, pos0,
                             shard=None):
    """W-token verification step for speculative decode: one block's
    forward over a chunk of W tokens per slot, K/V written through the
    block table at (``wblk``, ``woff``) [B, W] and attention read back
    through :func:`~deeplearning4j_tpu.kernels.paged_verify_attention`
    with query row j at position ``pos0 + j``.

    ``x`` is FLAT [B*W, d] — every matmul and layer norm here runs at
    the 2-D shapes that are row-bitwise-stable on the backends (the
    decode step's [b, d] @ W and a [B*W, d] @ W agree per row where a
    [B, W, d] batched contraction need not), and the attention unrolls
    per query row inside the kernel's reference path.  Together that
    makes this chunked step's outputs AND cache writes byte-identical
    to W sequential ``_block_decode_step_paged`` ticks — the invariant
    speculative greedy parity rests on.  ``shard`` replicates feature
    axes before their reductions exactly as in
    ``_block_decode_step_paged`` (the flat [B*W, d] rows keep their
    batch axis on ``data``)."""
    rep = shard.rep if shard is not None else (lambda t: t)
    BW, d = x.shape
    B, W = wblk.shape
    h, dh = ly.n_heads, d // ly.n_heads
    from deeplearning4j_tpu.kernels import (pad_head_dim,
                                            paged_verify_attention)
    cast = lambda w: w.astype(x.dtype)

    qkv = x @ cast(params["Wqkv"]) + cast(params["bqkv"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    split = lambda z: z.reshape(B, W, h, dh)
    q, k, v = split(q), split(k), split(v)
    width = kpool.shape[-1]         # past dh on the kernel route
    kpool = kpool.at[wblk, :, woff, :].set(pad_head_dim(k, width))
    vpool = vpool.at[wblk, :, woff, :].set(pad_head_dim(v, width))

    att = paged_verify_attention(q, kpool, vpool, table, pos0,
                                 scale=1.0 / (dh ** 0.5), shard=shard)
    att = rep(att.reshape(BW, d))
    att = att @ cast(params["Wo"]) + cast(params["bo"])
    hdn = _layer_norm(rep(x + att), params["ln1_g"], params["ln1_b"],
                      ly.eps)

    from deeplearning4j_tpu.nn.activations import get_activation
    act = get_activation(ly.activation or "gelu")
    ffn = act(hdn @ cast(params["W1"]) + cast(params["b1"]))
    ffn = rep(ffn) @ cast(params["W2"]) + cast(params["b2"])
    y = _layer_norm(rep(hdn + ffn), params["ln2_g"], params["ln2_b"],
                    ly.eps)
    return y, kpool, vpool


def _embed_prompt(ly: EmbeddingSequenceLayer, params, ids):
    """[b, t0] int prompt -> [b, t0, d] (positions 0..t0-1)."""
    y = jnp.take(params["W"], ids.astype(jnp.int32), axis=0)
    if ly.add_positional:
        y = y + params["P"][: ids.shape[1]][None]
    if ly.layer_norm:
        y = _layer_norm(y, params["g"], params["b"], ly.eps)
    return y


def _block_prefill(ly: TransformerEncoderBlock, params, x, shard=None):
    """Whole-prompt causal forward for one block: x [b, t, d] ->
    (y [b, t, d], k [b, h, t, dh], v) — ONE batched pass instead of t
    cached single-token steps, so prefill runs at matmul rate instead
    of the per-step params-bandwidth floor.  Same math (f32 scores,
    -1e9 mask) as ``_block_decode_step``.  ``shard`` replicates the
    feature axis before its reductions (mesh-sharded admissions; the
    returned K/V rows stay head-sharded for the pool scatter)."""
    rep = shard.rep if shard is not None else (lambda t: t)
    b, t, d = x.shape
    h, dh = ly.n_heads, d // ly.n_heads
    cast = lambda w: w.astype(x.dtype)
    qkv = x @ cast(params["Wqkv"]) + cast(params["bqkv"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    split = lambda z: z.reshape(b, t, h, dh).transpose(0, 2, 1, 3)
    q, k, v = split(q), split(k), split(v)
    scale = 1.0 / (dh ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    rows = jnp.arange(t)[:, None]
    cols = jnp.arange(t)[None, :]
    s = jnp.where((cols <= rows)[None, None], s, -1e9)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    att = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    att = rep(att.transpose(0, 2, 1, 3).reshape(b, t, d))
    att = att @ cast(params["Wo"]) + cast(params["bo"])
    hdn = _layer_norm(rep(x + att), params["ln1_g"], params["ln1_b"],
                      ly.eps)
    from deeplearning4j_tpu.nn.activations import get_activation
    act = get_activation(ly.activation or "gelu")
    ffn = act(hdn @ cast(params["W1"]) + cast(params["b1"]))
    ffn = rep(ffn) @ cast(params["W2"]) + cast(params["b2"])
    y = _layer_norm(rep(hdn + ffn), params["ln2_g"], params["ln2_b"],
                    ly.eps)
    return y, k, v


def _block_prefill_chunked(ly: TransformerEncoderBlock, params, x,
                           pk, pv, p0, shard=None):
    """Chunked (suffix) causal forward for one block: the query rows
    are the UNCACHED prompt suffix at global positions p0..p0+s-1 and
    the key set is [cached prefix K/V ; suffix K/V].  x: [b, s, d];
    ``pk``/``pv``: [b, h, P, dh] gathered prefix rows (valid cols
    < ``p0`` — the pad tail up to P is masked).  Same f32-score /
    -1e9-mask / f32-softmax math as ``_block_prefill``; masked columns
    contribute EXACT zeros to the softmax, so the suffix rows come out
    byte-identical to the full-prompt prefill's — the prefix-cache hit
    path's parity contract.  Returns (y, k, v) with k/v the SUFFIX
    rows only.  ``shard`` replicates feature axes before their
    reductions (the gathered prefix K/V arrive head-sharded from the
    mesh-sharded pool and concatenate exactly)."""
    rep = shard.rep if shard is not None else (lambda t: t)
    b, s_len, d = x.shape
    h, dh = ly.n_heads, d // ly.n_heads
    cast = lambda w: w.astype(x.dtype)
    qkv = x @ cast(params["Wqkv"]) + cast(params["bqkv"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    split = lambda z: z.reshape(b, s_len, h, dh).transpose(0, 2, 1, 3)
    q, k, v = split(q), split(k), split(v)
    P = pk.shape[2]
    kk = jnp.concatenate([pk, k], axis=2)       # [b, h, P+s, dh]
    vv = jnp.concatenate([pv, v], axis=2)
    scale = 1.0 / (dh ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kk).astype(jnp.float32) * scale
    cols = jnp.arange(P + s_len)
    col_g = jnp.where(cols < P, cols, p0 + cols - P)   # global key pos
    col_ok = jnp.where(cols < P, cols < p0, True)      # prefix pad out
    rows_g = p0 + jnp.arange(s_len)
    mask = col_ok[None, :] & (col_g[None, :] <= rows_g[:, None])
    s = jnp.where(mask[None, None], s, -1e9)
    p = jax.nn.softmax(s, axis=-1).astype(vv.dtype)
    att = jnp.einsum("bhqk,bhkd->bhqd", p, vv)
    att = rep(att.transpose(0, 2, 1, 3).reshape(b, s_len, d))
    att = att @ cast(params["Wo"]) + cast(params["bo"])
    hdn = _layer_norm(rep(x + att), params["ln1_g"], params["ln1_b"],
                      ly.eps)
    from deeplearning4j_tpu.nn.activations import get_activation
    act = get_activation(ly.activation or "gelu")
    ffn = act(hdn @ cast(params["W1"]) + cast(params["b1"]))
    ffn = rep(ffn) @ cast(params["W2"]) + cast(params["b2"])
    y = _layer_norm(rep(hdn + ffn), params["ln2_g"], params["ln2_b"],
                    ly.eps)
    return y, k, v


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


class TransformerGenerator:
    """Greedy / temperature / top-k / nucleus sampling with KV caches
    over a decoder MLN.  The prompt is prefilled in ONE batched causal
    forward (matmul-rate), then decode scans one token at a time.

    >>> gen = TransformerGenerator(net)
    >>> out = gen.generate(prompt_ids, n_new=64)      # [b, t0+64]
    >>> out = gen.generate(prompt_ids, n_new=64, temperature=0.8,
    ...                    top_k=40, top_p=0.95)
    """

    def __new__(cls, net, compute_dtype: Optional[str] = None):
        """A net whose middle layers are block RUNS of two kinds (Mamba
        and attention: ``nn/conf/layers_hybrid.py``) decodes through
        ``generation_runs.RunsGenerator``, which keeps this class's
        surface; the conf-identical rule then holds within a run."""
        if cls is TransformerGenerator:
            from deeplearning4j_tpu.models import generation_runs
            if generation_runs.is_run_stack(net):
                return super().__new__(generation_runs.RunsGenerator)
        return super().__new__(cls)

    # what a server sizes its K/V pool by; ``recurrent`` describes the
    # per-row state a stack of runs keeps besides (None here)
    recurrent = None
    kv_layers = property(lambda self: len(self.blocks))
    kv_heads = property(lambda self: self.blocks[0].n_heads)
    head_dim = property(lambda self: self.emb.n_out // self.kv_heads)
    vocab_size = property(
        lambda self: int(np.shape(self._params()[2]["W"])[-1]))

    def __init__(self, net, compute_dtype: Optional[str] = None):
        layers = list(net.layers)
        if not isinstance(layers[0], EmbeddingSequenceLayer):
            raise ValueError("generator expects EmbeddingSequenceLayer "
                             f"first, got {type(layers[0]).__name__}")
        if not all(isinstance(l, TransformerEncoderBlock)
                   for l in layers[1:-1]):
            raise ValueError("generator expects a pure "
                             "TransformerEncoderBlock stack")
        for l in layers[1:-1]:
            if not l.causal:
                raise ValueError("generation requires causal=True blocks")
        import dataclasses
        ref = dataclasses.asdict(layers[1])
        if any(dataclasses.asdict(l) != ref for l in layers[2:-1]):
            # the decode tick stacks the block params on a leading axis
            # and lax.scans over layers (ONE traced block body instead
            # of n_layers inlined copies) — that stack needs
            # conf-identical blocks.  Every in-tree decoder (zoo.Gpt)
            # is homogeneous.
            raise ValueError("generator requires conf-identical "
                             "TransformerEncoderBlocks (the decode "
                             "tick scans stacked block params)")
        self.net = net
        self.emb = layers[0]
        self.blocks = layers[1:-1]
        self.head = layers[-1]
        if not isinstance(self.head, OutputLayer):
            # RnnOutputLayer subclasses OutputLayer: any W/b softmax
            # head over the final hidden state decodes
            raise ValueError("generator expects an (Rnn)OutputLayer "
                             f"head, got {type(self.head).__name__}")
        self.compute_dtype = (jnp.dtype(compute_dtype)
                              if compute_dtype else jnp.float32)
        self._fn_cache = {}

    def _params(self):
        self.net._check_init()   # fires any lazy _param_sync_hook
        pt = self.net.params_tree
        n = len(self.net.layers)
        return (pt["layer_0"],
                [pt[f"layer_{i}"] for i in range(1, n - 1)],
                pt[f"layer_{n - 1}"])

    @staticmethod
    def _stack_blocks(blk_ps):
        """List of per-block param dicts -> one dict with a leading
        [n_layers] axis on every leaf — the layout ``_step``'s
        layer-scan consumes.  Inside jit the stack is a compile-time
        concatenate; the scan body then references ONE block's worth of
        program, so the decode tick's XLA program size stays O(1) in
        depth instead of inlining n_layers copies."""
        return jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *blk_ps)

    def _step(self, emb_p, blk_stack, head_p, kc, vc, tok, pos):
        """One decode tick.  ``blk_stack`` is ``_stack_blocks`` output;
        ``kc``/``vc`` are [n_layers, b, h, L, dh]; ``pos`` is a scalar
        (offline scan) or [b] vector (server slots).  Returns
        (logits [b, V], kc, vc)."""
        x = _embed_token(self.emb, emb_p, tok, pos)
        x = x.astype(self.compute_dtype)
        ly = self.blocks[0]          # conf-identical (checked in init)

        def body(h, layer):
            p, kc_l, vc_l = layer
            h, kc_l, vc_l = _block_decode_step(ly, p, kc_l, vc_l, h, pos)
            return h, (kc_l, vc_l)

        x, (kc, vc) = jax.lax.scan(body, x, (blk_stack, kc, vc))
        logits = (x.astype(jnp.float32) @ head_p["W"] + head_p["b"])
        return logits, kc, vc

    def _step_paged(self, emb_p, blk_stack, head_p, kc, vc, tok, pos,
                    table, wblk, woff, shard=None, kernel_writes=False):
        """Paged-pool decode tick: ``kc``/``vc`` are the global block
        pools [n_layers, n_blocks, h, block_size, dh], ``table``
        [b, max_blocks] the per-slot block tables, and the new row
        lands at (``wblk``, ``woff``) per slot.  Same layer-scan
        structure as ``_step``; attention routes through
        ``kernels.paged_decode_attention``.  ``shard`` (TpShardCtx)
        turns this into the mesh-sharded tick: embeds replicate, block
        math shards heads/columns along ``tp`` with explicit
        replication before feature reductions, and the logits gather
        so the sampler's argmax/sort runs on the full vocab row —
        byte-identical to the unsharded program by construction.

        ``kernel_writes`` (the server's decode scan) lets the kernel
        route CARRY the pools whole through the layer scan, written
        and read by the paged kernel alone: an XLA scatter of single
        rows makes XLA hold the loop's pool token-major inside a block,
        and the kernel's row-major operands then cost a slice, a layout
        copy and a write-back of a layer's pool per layer per tick
        (79% of the device's time before PR 26).  The reference routes
        (CPU, tp > 1) keep the per-layer slice + scatter + gather."""
        from deeplearning4j_tpu.kernels import paged_route
        x = _embed_token(self.emb, emb_p, tok, pos)
        x = x.astype(self.compute_dtype)
        if shard is not None:
            x = shard.rep(x)
        ly = self.blocks[0]          # conf-identical (checked in init)

        if kernel_writes and paged_route(shard) == "pallas":
            def body(carry, layer):
                h, kc, vc = carry
                p, l = layer
                h, kc, vc = _block_decode_step_paged(
                    ly, p, kc, vc, h, pos, table, wblk, woff,
                    shard=shard, layer=l)
                return (h, kc, vc), None

            (x, kc, vc), _ = jax.lax.scan(
                body, (x, kc, vc), (blk_stack, jnp.arange(kc.shape[0])))
        else:
            def body(h, layer):
                p, kc_l, vc_l = layer
                h, kc_l, vc_l = _block_decode_step_paged(
                    ly, p, kc_l, vc_l, h, pos, table, wblk, woff,
                    shard=shard)
                return h, (kc_l, vc_l)

            x, (kc, vc) = jax.lax.scan(body, x, (blk_stack, kc, vc))
        logits = (x.astype(jnp.float32) @ head_p["W"] + head_p["b"])
        if shard is not None:
            logits = shard.rep(logits)
        return logits, kc, vc

    def _verify_rows_paged(self, emb_p, blk_stack, head_p, kc, vc,
                           toks, pos0, epos, table, wblk, woff,
                           shard=None):
        """Speculative verification forward: ONE batched pass over a
        chunk of W tokens per slot — ``toks`` [B, W] (the anchor + the
        draft's proposals, inactive rows masked to 0), ``pos0`` [B]
        the chunk's base position per slot, ``epos`` [B, W] the embed
        positions (masked rows clamped to 0 so the positional take
        never reads out of bounds — the PR 2 NaN class), ``wblk`` /
        ``woff`` [B, W] the per-token write targets through the
        slot's block table (masked rows at the scratch block 0).

        Returns (logits [B, W, V], kc, vc): logits at EVERY chunk
        position — G_j is the target's distribution after consuming
        tokens 0..j, which is both the acceptance judge and the held
        logits the round hands forward.  Flat-row matmuls + the
        per-row attention contract (``_block_verify_step_paged``)
        make logits AND cache writes bitwise equal to W sequential
        ``_step_paged`` ticks."""
        B, W = toks.shape
        ly = self.blocks[0]
        flat_tok = toks.reshape(B * W).astype(jnp.int32)
        y = jnp.take(emb_p["W"], flat_tok, axis=0)
        if self.emb.add_positional:
            y = y + jnp.take(emb_p["P"], epos.reshape(B * W), axis=0)
        if self.emb.layer_norm:
            y = _layer_norm(y, emb_p["g"], emb_p["b"], self.emb.eps)
        x = y.astype(self.compute_dtype)
        if shard is not None:
            x = shard.rep(x)

        def body(h, layer):
            p, kc_l, vc_l = layer
            h, kc_l, vc_l = _block_verify_step_paged(
                ly, p, kc_l, vc_l, h, table, wblk, woff, pos0,
                shard=shard)
            return h, (kc_l, vc_l)

        x, (kc, vc) = jax.lax.scan(body, x, (blk_stack, kc, vc))
        logits = (x.astype(jnp.float32) @ head_p["W"] + head_p["b"])
        if shard is not None:
            logits = shard.rep(logits)
        return logits.reshape(B, W, -1), kc, vc

    def _prefill_rows_chunked(self, emb_p, blk_stack, head_p, suffix,
                              pk, pv, p0, last_ix, shard=None):
        """Chunked-prefill counterpart of ``_prefill_rows`` for
        prefix-cache HITS: ``suffix`` [b, s] are the uncached prompt
        tokens at global positions p0..p0+s-1 (pad tail beyond the
        real suffix), ``pk``/``pv`` [n_layers, b, h, P, dh] the cached
        prefix K/V gathered out of the block pool (valid cols < p0).
        Returns (logits [b, V] at local row ``last_ix`` = t0-p0-1, ks,
        vs [n_layers, b, h, s, dh]) — the SUFFIX rows only, for the
        caller to scatter into fresh blocks.  Prefill runs only on the
        suffix: the prefix's compute is the work the cache saves."""
        cd = self.compute_dtype
        ly = self.blocks[0]
        pos = p0 + jnp.arange(suffix.shape[1])
        y = jnp.take(emb_p["W"], suffix.astype(jnp.int32), axis=0)
        if self.emb.add_positional:
            # same rows _embed_prompt's [:t] slice reads; take clamps
            # the pad tail (finite garbage, masked before any read)
            y = y + jnp.take(emb_p["P"], pos, axis=0)
        if self.emb.layer_norm:
            y = _layer_norm(y, emb_p["g"], emb_p["b"], self.emb.eps)
        x = y.astype(cd)
        if shard is not None:
            x = shard.rep(x)

        def body(hdn, layer):
            p, pk_l, pv_l = layer
            hdn, k, v = _block_prefill_chunked(ly, p, hdn, pk_l, pv_l,
                                               p0, shard=shard)
            return hdn, (k.astype(cd), v.astype(cd))

        x, (ks, vs) = jax.lax.scan(body, x, (blk_stack, pk, pv))
        last = jax.lax.dynamic_slice_in_dim(x, last_ix, 1, axis=1)[:, 0]
        logits = last.astype(jnp.float32) @ head_p["W"] + head_p["b"]
        if shard is not None:
            logits = shard.rep(logits)
        return logits, ks, vs

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

    def _prefill_rows(self, emb_p, blk_stack, head_p, prompt, t0=None,
                      shard=None):
        """Batched prompt pass scanned over the stacked block params.
        Returns (logits [b, V], ks, vs [n_layers, b, h, t, dh]) — the
        raw per-layer K/V rows, for the caller to place (offline decode
        zero-pads to L; the generation server scatters into a slot's
        cache stripe).  ``t0`` picks the logits position for prompts
        PADDED past their real length (causal masking makes position
        t0-1 independent of the pad tail); default is the last column.
        THE prefill numerics both decode paths share — byte-identical
        greedy parity between them depends on exactly this."""
        cd = self.compute_dtype
        ly = self.blocks[0]
        x = _embed_prompt(self.emb, emb_p, prompt)
        x = x.astype(cd)
        if shard is not None:
            x = shard.rep(x)

        def body(hdn, p):
            hdn, k, v = _block_prefill(ly, p, hdn, shard=shard)
            return hdn, (k.astype(cd), v.astype(cd))

        x, (ks, vs) = jax.lax.scan(body, x, blk_stack)
        if t0 is None:
            last = x[:, -1]
        else:
            last = jax.lax.dynamic_slice_in_dim(x, t0 - 1, 1,
                                                axis=1)[:, 0]
        logits = last.astype(jnp.float32) @ head_p["W"] + head_p["b"]
        if shard is not None:
            logits = shard.rep(logits)
        return logits, ks, vs

    def _prefill(self, emb_p, blk_stack, head_p, prompt, L):
        """``_prefill_rows`` + zero-padded caches out to length L,
        stacked [n_layers, b, h, L, dh] — ``_step``'s layout."""
        b = prompt.shape[0]
        h = self.blocks[0].n_heads
        dh = self.emb.n_out // h
        n_layers = len(self.blocks)
        cd = self.compute_dtype
        logits, ks, vs = self._prefill_rows(emb_p, blk_stack, head_p,
                                            prompt)
        kc = jnp.zeros((n_layers, b, h, L, dh), cd)
        vc = jnp.zeros((n_layers, b, h, L, dh), cd)
        kc = jax.lax.dynamic_update_slice(kc, ks, (0, 0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, vs, (0, 0, 0, 0, 0))
        return logits, kc, vc

    def _generate_scan(self, emb_p, blk_ps, head_p, ids, rng_key,
                       t0, n_new, L, temperature, top_k=None,
                       top_p=None):
        if self.compute_dtype != jnp.float32:
            # cast the full parameter set ONCE inside the program: the
            # decode scan re-reads every parameter each tick, and
            # streaming f32-stored weights costs 2x the bytes of the
            # bf16 math actually performed (measured 840 -> 969
            # steps/s on zoo.Gpt; the tick also carries per-op
            # overheads the byte halving cannot remove)
            emb_p, blk_ps, head_p = _cast_floating(
                (emb_p, blk_ps, head_p), self.compute_dtype)
        blk_stack = self._stack_blocks(blk_ps)
        prompt = ids[:, :t0]
        logits0, kc, vc = self._prefill(emb_p, blk_stack, head_p,
                                        prompt, L)

        def body(carry, pos):
            # sample the token AT pos from the previous logits, write
            # it, embed it, advance the caches
            ids, kc, vc, key, logits = carry
            nxt, key = _draw_token(logits, key, temperature, top_k,
                                   top_p)
            ids = jax.lax.dynamic_update_slice(ids, nxt[:, None],
                                               (0, pos))
            logits, kc, vc = self._step(emb_p, blk_stack, head_p,
                                        kc, vc, nxt, pos)
            return (ids, kc, vc, key, logits), None

        (ids, _, _, _, _), _ = jax.lax.scan(
            body, (ids, kc, vc, rng_key, logits0),
            t0 + jnp.arange(n_new))
        return ids
