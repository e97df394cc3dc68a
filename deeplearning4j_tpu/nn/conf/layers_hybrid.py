"""Pre-norm decoder blocks for hybrid state-space / attention stacks.

Two blocks, each ``x = x + Mixer(RMSNorm(x))`` then ``x = x +
FFN(RMSNorm(x))``, no biases:

* ``MambaBlockRun`` -- the mixer is a Mamba-1 selective state-space
  layer (input projection, causal depthwise convolution, input-dependent
  step size and B / C, a diagonal recurrence over a ``d_state``-wide
  state per channel, gated output projection; RMSNorm on dt, B and C).
* ``AttentionBlockRun`` -- the mixer is causal softmax attention with
  grouped query heads (``n_heads`` query heads on ``n_kv_heads`` K/V
  heads).  By its conf it may also have keys ``qk_dim`` wide beside
  values ``v_dim`` wide, a rotary term on the first ``rotary_dim``
  lanes of q and k (rotate-half pairing, keys cached rotated), a scale
  on the values, a sliding ``window`` (a query reads the ``window``
  newest positions, itself included), a learned ``sink`` logit per
  query head that enters the softmax's denominator and nothing else, a
  scaled rotary base (``rope_scaling``: YaRN) and a sigmoid ``gate`` per
  query head on the attention's output, read off the layer's normed
  input.

The FFN is a dense SwiGLU, or -- ``n_experts`` set -- a ROUTED one: a
sigmoid router over all ``n_experts`` in float32, the ``top_k`` of
``score + bias`` selected and weighted by their scores normalised, and
the experts this chip HOLDS (``held`` = (first, count): what expert
parallelism tells a layer) computed by ``kernels.expert_ffn``.  What
the experts held elsewhere would add is not here to add: the layer
returns its own part of the sum, which is all there is on one chip.
``routed_scale`` multiplies the routed sum, and ``shared_ff`` sets a
SHARED expert that wide beside the routed ones, which every row takes.

A layer conf here is a RUN of ``n_blocks`` identical blocks: its
parameters carry a leading ``[n_blocks]`` axis as ``init()`` makes
them and ``apply()`` is one ``lax.scan`` over it.  A 28-layer decoder
is then a handful of layers (``emb, Mamba x 7, Attn, Mamba x 13, Attn,
Mamba x 6, head``), its parameter tree is stacked as made -- a serving
snapshot aliases it instead of stacking a second copy -- and a decode
tick compiles one short scan a run.

Each block is written ONCE: ``sequence()`` is its forward over whole
sequences (what ``apply()`` scans, and the prefill), ``step()`` its
forward for one new token per row.  Both take the cache or state
access as an argument, so the offline generator and the serving
scheduler call the same two functions:

* attention's ``step(p, x, attend)``: ``attend(q, k, v) -> (att,
  cache)`` writes the token's K/V row and reads the context (a dense
  cache offline, the paged pool in the server);
* Mamba's ``step(p, x, rec, layer, active)``: ``rec`` is the WHOLE
  stacked recurrent state ``{"h": [layers, b, d_state, d_inner] f32,
  "conv": [layers, b, d_conv - 1, d_inner]}`` and ``layer`` this
  block's index in it (``kernels.ssm_step`` updates the one layer in
  place); rows where ``active`` is false keep their state bit for bit.

``TiedLMHead`` is the final RMSNorm and the product with the embedding
table transposed: it owns the norm's gain and READS the table of the
layer ``tied_to`` names, so no second table exists.  ``LMHead`` is the
same with a ``[vocab, d]`` matrix of its own (untied).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.base import BaseLayerConf, register_layer
from deeplearning4j_tpu.nn.weights_init import init_weights


def rms_norm(x, g, eps: float):
    """``g * x / sqrt(mean(x^2) + eps)`` at >= float32, in x's dtype."""
    ct = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(ct)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(ct)).astype(x.dtype)


#: time steps one compiled loop iteration of the prefill's scan holds
_SCAN_UNROLL = 8


def _silu(x):
    return x * jax.nn.sigmoid(x)


@dataclasses.dataclass
class _PreNormRun(BaseLayerConf):
    """What the two runs share: the run length, the SwiGLU half of a
    block and ``apply()``."""

    n_blocks: int = 1
    d_ff: Optional[int] = None       # default 4 * d
    n_in: Optional[int] = None
    n_out: Optional[int] = None
    eps: float = 1e-6
    # a routed feed-forward: the router's outputs, the experts a token
    # takes, and (first, count) of the experts held here (None: all);
    # d_ff is then an expert's width
    n_experts: Optional[int] = None
    top_k: int = 1
    held: Optional[tuple] = None
    # beside the routed experts: a factor on their weighted sum, and a
    # shared expert (its width) that every row takes
    routed_scale: Optional[float] = None
    shared_ff: Optional[int] = None

    WANTED_KINDS = ("rnn",)
    RECURRENT = False                # keeps per-row state besides K/V

    def infer_shapes(self, input_shape):
        t, f = input_shape
        self.n_in = self.n_out = int(f)
        if self.d_ff is None:
            self.d_ff = 4 * self.n_in
        self._check_widths()
        return (t, self.n_out)

    def _check_widths(self):
        pass

    def has_params(self):
        return True

    def regularized_param_names(self):
        return ()

    def init(self, key, dtype=jnp.float32):
        # one program a run, not one a leaf
        return jax.jit(lambda k: self._init(k, dtype))(key), {}

    def _matrix(self, key, shape, dtype):
        n = self.n_blocks
        return init_weights(key, (n,) + shape, shape[0], shape[-1],
                            self.weight_init, dtype,
                            self.weight_distribution)

    @property
    def held_experts(self) -> tuple:
        """(first, count) of the experts this chip holds."""
        if self.held is None:
            return (0, self.n_experts)
        first, count = (int(v) for v in self.held)
        if not (0 <= first and 1 <= count
                and first + count <= self.n_experts):
            raise ValueError(f"held={self.held} lies outside the "
                             f"{self.n_experts} experts")
        return (first, count)

    def _ffn_params(self, keys, dtype):
        d, ff, n = self.n_in, self.d_ff, self.n_blocks
        if self.n_experts is None:
            return {"norm2": jnp.ones((n, d), dtype),
                    "W_gate": self._matrix(keys[0], (d, ff), dtype),
                    "W_up": self._matrix(keys[1], (d, ff), dtype),
                    "W_down": self._matrix(keys[2], (ff, d), dtype)}
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"top_k {self.top_k} of {self.n_experts} "
                             "experts")
        held = self.held_experts[1]
        kr, kg, ku, kd = jax.random.split(keys[0], 4)
        experts = lambda key, shape: init_weights(
            key, (n, held) + shape, shape[0], shape[-1], self.weight_init,
            dtype, self.weight_distribution)
        shared = {}
        if self.shared_ff:
            sg, su, sd = jax.random.split(keys[1], 3)
            sff = self.shared_ff
            shared = {"Ws_gate": self._matrix(sg, (d, sff), dtype),
                      "Ws_up": self._matrix(su, (d, sff), dtype),
                      "Ws_down": self._matrix(sd, (sff, d), dtype)}
        return {"norm2": jnp.ones((n, d), dtype),
                "W_router": self._matrix(kr, (d, self.n_experts), dtype),
                "e_bias": jnp.zeros((n, self.n_experts), dtype),
                "W_gate": experts(kg, (d, ff)),
                "W_up": experts(ku, (d, ff)),
                "W_down": experts(kd, (ff, d)), **shared}

    #: leaves of a routed run that a scan over its layers leaves WHOLE
    #: (``whole_leaves``): the kernel reads a layer's experts out of the
    #: stacked array by the layer's index
    WHOLE = ("W_gate", "W_up", "W_down")

    def whole_leaves(self, p) -> dict:
        """The leaves of a run's stacked parameters ``p`` that a scan
        over its layers should close over, not slice -- a routed run's
        expert matrices: handed to ``sequence()`` / ``step()`` whole,
        with ``layer=`` the layer's index in the run."""
        if self.n_experts is None:
            return {}
        return {k: p[k] for k in self.WHOLE}

    def _ffn(self, p, x, live=None, layer=None):
        """(x + FFN(RMSNorm(x)), tally).  Dense: ``tally`` is None.
        Routed: rows where ``live`` is false take no expert, and
        ``tally`` is int32 [count + 1] -- the rows each held expert got,
        then all the token-expert pairs the router made (held here or
        elsewhere).  With ``layer`` the expert matrices of ``p`` are the
        whole run's, this layer's at that index."""
        n = rms_norm(x, p["norm2"], self.eps)
        w = lambda k: p[k].astype(x.dtype)
        swiglu = lambda h, gate, up, down: \
            (_silu(h @ w(gate)) * (h @ w(up))) @ w(down)
        if self.n_experts is None:
            return x + swiglu(n, "W_gate", "W_up", "W_down"), None
        from deeplearning4j_tpu.kernels import expert_ffn
        first, count = self.held_experts
        rows = n.reshape(-1, n.shape[-1])
        with jax.named_scope("expert_route"):
            # float32 whatever the compute dtype: a top-k among many
            # near-equal scores flips on rounding
            f32 = jnp.float32
            r = jax.nn.sigmoid(jnp.matmul(
                rows.astype(f32), p["W_router"].astype(f32),
                precision=jax.lax.Precision.HIGHEST))
            _, idx = jax.lax.top_k(r + p["e_bias"].astype(f32), self.top_k)
            picked = jnp.take_along_axis(r, idx, axis=-1)
            weight = picked / jnp.sum(picked, axis=-1, keepdims=True)
            if self.routed_scale is not None:
                weight = weight * self.routed_scale
            alive = (jnp.ones(rows.shape[:1], bool) if live is None
                     else live.reshape(-1))
            here = (idx >= first) & (idx < first + count) & alive[:, None]
            local = jnp.where(here, idx - first, count).astype(jnp.int32)
            per_expert = jnp.sum(
                local[..., None] == jnp.arange(count), axis=(0, 1))
            tally = jnp.concatenate(
                [per_expert, self.top_k * jnp.sum(alive)[None]]).astype(
                    jnp.int32)
        out = expert_ffn(rows, local, jnp.where(here, weight, 0.0),
                         p["W_gate"], p["W_up"], p["W_down"], layer)
        if self.shared_ff:
            with jax.named_scope("expert_shared"):
                out = out + swiglu(rows, "Ws_gate", "Ws_up", "Ws_down")
        return x + out.reshape(x.shape), tally

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None, mask=None):
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        y, _ = jax.lax.scan(
            lambda h, p: (self.sequence(p, h)[0], None), x, params)
        return y, state


#: a sequence longer than this is attended a block of queries at a
#: time: its [b, heads, t, t] float32 scores are not built whole
_QUERY_BLOCK = 256


def yarn_inv_freq(rotary_dim: int, theta: float, scaling: dict):
    """(inv_freq float64 [rotary_dim / 2], the factor on cos and sin) of
    a YaRN-scaled base, as ``transformers``' ``_compute_yarn_parameters``
    has it: the pairs that turn fewer than ``beta_slow`` times over the
    ``original_max_position_embeddings`` are interpolated (divided by
    ``factor``), those that turn more than ``beta_fast`` times are left,
    a linear ramp between (the bounds floored and ceiled)."""
    import numpy as np
    kind = scaling.get("rope_type", scaling.get("type"))
    if kind != "yarn":
        raise ValueError(f"rope_scaling {kind!r}: only 'yarn' is built")
    d, factor = rotary_dim, float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])
    fast = float(scaling.get("beta_fast") or 32)
    slow = float(scaling.get("beta_slow") or 1)
    att = scaling.get("attention_factor")
    if att is None:
        att = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    turns = lambda r: d * math.log(orig / (r * 2 * math.pi)) \
        / (2 * math.log(theta))
    low, high = math.floor(turns(fast)), math.ceil(turns(slow))
    low, high = max(low, 0), min(high, d - 1)
    if low == high:
        high += 0.001
    i = np.arange(d // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / d)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp), float(att)


def rotate_half(x, pos, rotary_dim: int, theta: float, scaling=None):
    """The rotary term on the first ``rotary_dim`` lanes of x [...,
    heads, dim] at positions ``pos`` [...]: lane i pairs with lane
    i + rotary_dim / 2 (rotate-half), angle ``pos * theta ** (-i /
    (rotary_dim / 2))``; in float32, back in x's dtype.  With
    ``scaling`` (a ``rope_scaling`` dict) the frequencies and the factor
    on cos and sin are :func:`yarn_inv_freq`'s."""
    half = rotary_dim // 2
    f32 = jnp.float32
    if scaling is None:
        inv = jnp.exp(jnp.arange(half, dtype=f32)
                      * (-math.log(theta) / half))
    else:
        inv, factor = yarn_inv_freq(rotary_dim, theta, scaling)
    ang = jnp.asarray(pos, f32)[..., None, None] * jnp.asarray(inv, f32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None:
        cos, sin = cos * factor, sin * factor
    x1, x2 = x[..., :half].astype(f32), x[..., half:rotary_dim].astype(f32)
    return jnp.concatenate(
        [(x1 * cos - x2 * sin).astype(x.dtype),
         (x2 * cos + x1 * sin).astype(x.dtype), x[..., rotary_dim:]], axis=-1)


@register_layer
@dataclasses.dataclass
class AttentionBlockRun(_PreNormRun):
    """``n_blocks`` x [grouped-query causal attention + FFN].  As its
    defaults stand: no positional term (in a hybrid stack the recurrent
    layers carry the order), keys and values ``head_dim`` wide, full
    attention, no sink, a dense SwiGLU."""

    n_heads: int = 8
    n_kv_heads: int = 1
    head_dim: Optional[int] = None   # default d / n_heads
    qk_dim: Optional[int] = None     # default head_dim
    v_dim: Optional[int] = None      # default head_dim
    rotary_dim: Optional[int] = None  # lanes of q / k that rotate
    rope_theta: float = 10000.0
    value_scale: Optional[float] = None
    window: Optional[int] = None     # None: full attention
    sink: bool = False               # a learned logit per query head
    rope_scaling: Optional[dict] = None   # {"rope_type": "yarn", ...}
    gate: bool = False               # sigmoid(n Wg) a query head on att

    #: why a server cannot share, restore, re-verify or shard this
    #: kind's K/V rows (None would mean it can)
    REFUSES = ("AttentionBlockRun layers: the run has no "
               "sequence(prefix=) over cached K/V rows (rotated keys "
               "and a window's ring of one or several blocks are not "
               "restorable from a shared prefix yet), no W-row verify "
               "step() and no shard points: a gate's heads, a shared "
               "expert and held experts are not split over tp (ROADMAP "
               "M1, M2, M3, M4)")

    def _check_widths(self):
        if self.head_dim is None:
            if self.n_in % self.n_heads:
                raise ValueError(f"d_model {self.n_in} must divide by "
                                 f"n_heads {self.n_heads}")
            self.head_dim = self.n_in // self.n_heads
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads {self.n_heads} must divide by "
                             f"n_kv_heads {self.n_kv_heads}")
        self.qk_dim = self.qk_dim or self.head_dim
        self.v_dim = self.v_dim or self.head_dim
        if self.rotary_dim is not None and (
                self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.qk_dim):
            raise ValueError(f"rotary_dim {self.rotary_dim} must be even "
                             f"and at most qk_dim {self.qk_dim}")
        if self.window is not None and self.window < 1:
            raise ValueError("window must be >= 1")

    def _init(self, key, dtype):
        d, n = self.n_in, self.n_blocks
        hq, hkv, dk, dv = (self.n_heads, self.n_kv_heads, self.qk_dim,
                           self.v_dim)
        ks = jax.random.split(key, 7)
        sink = ({"sink": jnp.zeros((n, hq), dtype)} if self.sink else {})
        gate = ({"Wg": self._matrix(jax.random.fold_in(key, 7), (d, hq),
                                    dtype)} if self.gate else {})
        return {"norm1": jnp.ones((n, d), dtype),
                "Wq": self._matrix(ks[0], (d, hq * dk), dtype),
                "Wk": self._matrix(ks[1], (d, hkv * dk), dtype),
                "Wv": self._matrix(ks[2], (d, hkv * dv), dtype),
                "Wo": self._matrix(ks[3], (hq * dv, d), dtype),
                **sink, **gate, **self._ffn_params(ks[4:], dtype)}

    def _qkv(self, p, x, pos=None):
        """q [..., n_heads, qk_dim], k [..., n_kv_heads, qk_dim] -- both
        rotated at ``pos`` [...] where the run has a rotary term -- v
        [..., n_kv_heads, v_dim], scaled, and the gate [..., n_heads] on
        the heads' outputs (None: the run has none)."""
        n = rms_norm(x, p["norm1"], self.eps)
        lead = x.shape[:-1]
        hq, hkv, dk, dv = (self.n_heads, self.n_kv_heads, self.qk_dim,
                           self.v_dim)
        q = (n @ p["Wq"].astype(x.dtype)).reshape(lead + (hq, dk))
        k = (n @ p["Wk"].astype(x.dtype)).reshape(lead + (hkv, dk))
        v = (n @ p["Wv"].astype(x.dtype)).reshape(lead + (hkv, dv))
        if self.rotary_dim is not None:
            q, k = (rotate_half(z, pos, self.rotary_dim, self.rope_theta,
                                self.rope_scaling) for z in (q, k))
        if self.value_scale is not None:
            v = v * jnp.asarray(self.value_scale, v.dtype)
        gate = None
        if self.gate:
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(n @ p["Wg"].astype(x.dtype))
        return q, k, v, gate

    def _out(self, p, x, att, gate):
        """x + (att [..., n_heads, v_dim], each head's rows times its
        gate) Wo."""
        if gate is not None:
            with jax.named_scope("attn_gate"):
                att = att * gate[..., None].astype(att.dtype)
        return x + att.reshape(x.shape[:-1] + (-1,)) \
            @ p["Wo"].astype(x.dtype)

    def _attend_block(self, qg, k, v, q_pos, sink):
        """The queries qg [b, tq, hkv, g, dk] at positions ``q_pos``
        [tq] over all of k / v [b, t, hkv, .]: float32 scores, a -1e9
        mask (causal, and the window's band), softmax in float32 with
        the sink's logit in the denominator."""
        from deeplearning4j_tpu.kernels import softmax_with_sink
        t = k.shape[1]
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
        s = s * (1.0 / math.sqrt(self.qk_dim))
        k_pos = jnp.arange(t)[None, :]
        seen = k_pos <= q_pos[:, None]
        if self.window is not None:
            seen = seen & (k_pos > q_pos[:, None] - self.window)
        s = jnp.where(seen[None, None, None], s, -1e9)
        w = softmax_with_sink(s, sink)
        return jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v)

    def prefill_route(self, t: int, shard=None) -> str:
        """'kernel' | 'dense': how ``sequence`` attends ``t`` positions.
        The kernel (``kernels.flash_prefill``: the band's and the causal
        triangle's key blocks alone, K/V heads grouped in its index
        maps) where the kernel route is on (``kernels.paged_route``: the
        TPU, as for the paged pool), the prompt is longer than one
        query block, there is no sink, and keys and values are equally
        wide in whole 128-lane rows; else ``_attend_block`` over every
        key, a query block at a time."""
        from deeplearning4j_tpu.kernels import paged_route
        if (t > _QUERY_BLOCK and not self.sink
                and self.qk_dim == self.v_dim and self.qk_dim % 128 == 0
                and paged_route(shard) == "pallas"):
            return "kernel"
        return "dense"

    def sequence(self, p, x, t0=None, shard=None, layer=None):
        """x [b, t, d] -> (y, {"k", "v"}): of a full-attention run the
        rows [b, n_kv_heads, t, .] as a pool holds them (keys rotated).
        Causal, so ``t0`` (a padded prompt's real length) changes
        nothing a real position reads.  Of a WINDOW run the rows are
        its cache AS AFTER TOKEN ``t0`` (default: the last): [b,
        n_kv_heads, window, .], row j the newest position p < t0 with
        p % window == j (zero where there is none) -- a ring, which
        softmax does not mind, written on at ``pos % window``.  A routed
        run gives "routed" too, ``_ffn``'s tally over the positions
        before ``t0`` (``layer``: as ``_ffn`` takes it).  No ``prefix=`` form and no shard points
        (``shard`` names a device, never a split): ``REFUSES``.  A long
        prompt is attended as ``prefill_route`` says: on the kernel
        route the forward has no VJP (inference alone)."""
        b, t, _ = x.shape
        hq, hkv = self.n_heads, self.n_kv_heads
        q, k, v, gate = self._qkv(p, x, jnp.arange(t))
        qg = q.reshape(b, t, hkv, hq // hkv, self.qk_dim)
        sink = p["sink"] if self.sink else None
        if t <= _QUERY_BLOCK:
            att = self._attend_block(qg, k, v, jnp.arange(t), sink)
        elif self.prefill_route(t, shard) == "kernel":
            from deeplearning4j_tpu.kernels import flash_prefill
            att = flash_prefill(q, k, v, window=self.window,
                                scale=1.0 / math.sqrt(self.qk_dim))
        else:
            nq = -(-t // _QUERY_BLOCK)
            pad = nq * _QUERY_BLOCK - t
            blocks = jnp.pad(qg, ((0, 0), (0, pad)) + ((0, 0),) * 3).reshape(
                (b, nq, _QUERY_BLOCK) + qg.shape[2:]).swapaxes(0, 1)
            att = jax.lax.map(
                lambda a: self._attend_block(
                    a[0], k, v, a[1] * _QUERY_BLOCK
                    + jnp.arange(_QUERY_BLOCK), sink),
                (blocks, jnp.arange(nq)))
            att = att.swapaxes(0, 1).reshape(
                (b, nq * _QUERY_BLOCK) + att.shape[3:])[:, :t]
        x = self._out(p, x, att.reshape(b, t, hq, self.v_dim), gate)
        live = None if t0 is None else jnp.broadcast_to(
            jnp.arange(t) < t0, (b, t))
        y, tally = self._ffn(p, x, live, layer)
        rows = lambda z: z.transpose(0, 2, 1, 3)
        if self.window is not None:
            last = (t if t0 is None else t0) - 1
            j = jnp.arange(self.window)
            at = last - (last - j) % self.window      # < 0: none yet
            rows = lambda z: jnp.where(
                (at >= 0)[None, None, :, None],
                jnp.take(z, jnp.clip(at, 0, t - 1), axis=1)
                .transpose(0, 2, 1, 3), 0)
        got = {"k": rows(k), "v": rows(v)}
        if tally is not None:
            got["routed"] = tally
        return y, got

    def step(self, p, x, attend, shard=None, pos=None, live=None,
             layer=None):
        """x [b, d], one new token per row at positions ``pos`` [b] (a
        rotary run needs them).  ``attend(q [b, n_heads, qk_dim], k [b,
        n_kv_heads, qk_dim], v [b, n_kv_heads, v_dim]) -> (att [b,
        n_heads, v_dim], cache)`` writes the row and reads the context;
        a run with a sink hands it its logits as ``sink=``.  Returns
        (y, cache) and, of a routed run, ``_ffn``'s tally over the
        ``live`` rows as a third (``layer``: as ``_ffn`` takes it)."""
        q, k, v, gate = self._qkv(p, x, pos)
        att, cache = (attend(q, k, v, sink=p["sink"]) if self.sink
                      else attend(q, k, v))
        x = self._out(p, x, att, gate)
        y, tally = self._ffn(p, x, live, layer)
        return (y, cache) if tally is None else (y, cache, tally)


@register_layer
@dataclasses.dataclass
class MambaBlockRun(_PreNormRun):
    """``n_blocks`` x [Mamba-1 mixer + SwiGLU].  ``delta``, the
    exponential, the recurrence and the state are float32 whatever the
    compute dtype; the convolution's window is kept in the compute
    dtype."""

    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None    # default ceil(d / 16)

    RECURRENT = True
    #: the same question, for the state this kind keeps besides
    REFUSES = ("recurrent (state-space) layers: shared or restored K/V "
               "blocks cannot restore a slot's recurrent state (snapshots "
               "of it at block boundaries are later work), and the state "
               "is not sharded")

    def _check_widths(self):
        if self.n_experts is not None:
            raise ValueError("a routed feed-forward on a recurrent run is "
                             "not supported (its tally is not carried)")
        if self.dt_rank is None:
            self.dt_rank = -(-self.n_in // 16)

    @property
    def d_inner(self) -> int:
        return self.expand * self.n_in

    def _init(self, key, dtype):
        """Matrices by the configured scheme; the recurrence as the
        Mamba paper starts it: ``A = -(1 .. d_state)`` per channel,
        ``softplus(dt_bias)`` log-uniform in [1e-3, 0.1], ``D = 1``."""
        d, n, di = self.n_in, self.n_blocks, self.d_inner
        ns, r = self.d_state, self.dt_rank
        ks = jax.random.split(key, 9)
        step = jnp.exp(jax.random.uniform(ks[5], (n, di), jnp.float32)
                       * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        a_log = jnp.log(jnp.arange(1, ns + 1, dtype=jnp.float32))
        return {"norm1": jnp.ones((n, d), dtype),
                "W_in": self._matrix(ks[0], (d, 2 * di), dtype),
                "conv_w": self._matrix(ks[1], (self.d_conv, di), dtype),
                "conv_b": jnp.zeros((n, di), dtype),
                "W_x": self._matrix(ks[2], (di, r + 2 * ns), dtype),
                "dt_norm": jnp.ones((n, r), dtype),
                "b_norm": jnp.ones((n, ns), dtype),
                "c_norm": jnp.ones((n, ns), dtype),
                "W_dt": self._matrix(ks[3], (r, di), dtype),
                # the inverse of softplus
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
                "A_log": jnp.broadcast_to(a_log[None, :, None],
                                          (n, ns, di)).astype(dtype),
                "D": jnp.ones((n, di), dtype),
                "W_out": self._matrix(ks[4], (di, d), dtype),
                **self._ffn_params(ks[6:], dtype)}

    def _inputs(self, p, x):
        n = rms_norm(x, p["norm1"], self.eps)
        u, z = jnp.split(n @ p["W_in"].astype(x.dtype), 2, axis=-1)
        return u, z

    def _selective(self, p, uc):
        """The convolved input's step size (before bias and softplus,
        float32), B and C."""
        r, ns = self.dt_rank, self.d_state
        xdbc = uc @ p["W_x"].astype(uc.dtype)
        dt_r = rms_norm(xdbc[..., :r], p["dt_norm"], self.eps)
        bm = rms_norm(xdbc[..., r:r + ns], p["b_norm"], self.eps)
        cm = rms_norm(xdbc[..., r + ns:], p["c_norm"], self.eps)
        dt = jnp.matmul(dt_r, p["W_dt"].astype(uc.dtype),
                        preferred_element_type=jnp.float32)
        return dt, bm, cm

    def _conv(self, p, taps):
        """``taps``: the ``d_conv`` shifted views of the input, oldest
        first.  silu(bias + sum of tap x weight)."""
        w = p["conv_w"].astype(taps[0].dtype)
        acc = p["conv_b"].astype(taps[0].dtype)
        for j, tap in enumerate(taps):
            acc = acc + tap * w[j]
        return _silu(acc)

    def sequence(self, p, x, t0=None):
        """x [b, t, d] -> (y, {"h" [b, d_state, d_inner] f32, "conv"
        [b, d_conv - 1, d_inner]}): the state AS AFTER TOKEN ``t0``
        (default: the last) -- a padded prompt's pad positions do not
        advance it.  The recurrence is a sequential ``lax.scan`` over
        time."""
        f32 = jnp.float32
        b, t, _ = x.shape
        k = self.d_conv
        u, z = self._inputs(p, x)
        up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
        uc = self._conv(p, [up[:, j:j + t] for j in range(k)])
        dt, bm, cm = self._selective(p, uc)
        delta = jax.nn.softplus(dt + p["dt_bias"].astype(f32))
        if t0 is not None:
            delta = jnp.where((jnp.arange(t) < t0)[None, :, None],
                              delta, 0.0)
        a = -jnp.exp(p["A_log"].astype(f32))                # [n, di]
        ucf = uc.astype(f32)

        def tick(h, xs):
            d_t, du_t, b_t, c_t = xs
            h = jnp.exp(d_t[:, None, :] * a) * h \
                + du_t[:, None, :] * b_t[:, :, None]
            return h, jnp.sum(h * c_t[:, :, None], axis=1)

        tm = lambda v: jnp.swapaxes(v, 0, 1)                # time first
        h, y = jax.lax.scan(
            tick, jnp.zeros((b, self.d_state, self.d_inner), f32),
            (tm(delta), tm(delta * ucf), tm(bm.astype(f32)),
             tm(cm.astype(f32))), unroll=_SCAN_UNROLL)
        y = tm(y) + p["D"].astype(f32) * ucf
        zf = z.astype(f32)
        gated = (y * _silu(zf)).astype(x.dtype)
        x = x + gated @ p["W_out"].astype(x.dtype)
        last = t if t0 is None else t0
        win = jax.lax.dynamic_slice_in_dim(up, last, k - 1, axis=1)
        return self._ffn(p, x)[0], {"h": h, "conv": win}

    def step(self, p, x, rec, layer, active):
        """x [b, d], one new token per row; ``rec`` the whole stacked
        state, ``layer`` this block's index in it.  Returns (y, rec)."""
        from deeplearning4j_tpu.kernels import ssm_step
        f32 = jnp.float32
        u, z = self._inputs(p, x)
        win = jax.lax.dynamic_index_in_dim(rec["conv"], layer, 0,
                                           keepdims=False)
        taps = [win[:, j] for j in range(self.d_conv - 1)] + [u]
        uc = self._conv(p, [tap.astype(u.dtype) for tap in taps])
        shifted = jnp.concatenate(
            [win[:, 1:], u[:, None].astype(win.dtype)], axis=1)
        conv = jax.lax.dynamic_update_index_in_dim(
            rec["conv"], jnp.where(active[:, None, None], shifted, win),
            layer, 0)
        dt, bm, cm = self._selective(p, uc)
        gated, h = ssm_step(rec["h"], layer, dt, uc, bm, cm, z,
                            -jnp.exp(p["A_log"].astype(f32)), p["D"],
                            p["dt_bias"], active)
        x = x + gated @ p["W_out"].astype(x.dtype)
        return self._ffn(p, x)[0], {"h": h, "conv": conv}


@register_layer
@dataclasses.dataclass
class TiedLMHead(BaseLayerConf):
    """Final RMSNorm, then logits against the embedding table of layer
    ``tied_to`` transposed (tied word embeddings).  An inference head:
    it has no loss, so ``fit()`` refuses a net that ends in it."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None      # vocabulary size
    tied_to: int = 0
    eps: float = 1e-6

    WANTED_KINDS = ("rnn",)
    OUTPUT_KIND = "rnn"

    def infer_shapes(self, input_shape):
        t, f = input_shape
        self.n_in = int(f)
        return (t, self.n_out)

    def has_params(self):
        return True

    def regularized_param_names(self):
        return ()

    def init(self, key, dtype=jnp.float32):
        return {"g": jnp.ones((self.n_in,), dtype)}, {}

    def logits(self, params, table, x):
        """float32 logits of x [..., d] against ``table`` [vocab, d]:
        operands in x's dtype, accumulated in float32."""
        n = rms_norm(x, params["g"], self.eps)
        return jax.lax.dot_general(
            n, table.astype(n.dtype),
            (((n.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None, tied=None):
        if tied is None:
            raise ValueError("TiedLMHead reads the table of layer "
                             f"{self.tied_to}: apply() needs tied=")
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        return self.logits(params, tied["W"], x), state


@register_layer
@dataclasses.dataclass
class LMHead(TiedLMHead):
    """Final RMSNorm, then logits against the head's OWN ``[vocab, d]``
    matrix (untied word embeddings).  An inference head like
    ``TiedLMHead``."""

    tied_to: Optional[int] = None

    def init(self, key, dtype=jnp.float32):
        w = init_weights(key, (self.n_out, self.n_in), self.n_in, self.n_out,
                         self.weight_init, dtype, self.weight_distribution)
        return {"g": jnp.ones((self.n_in,), dtype), "W": w}, {}

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None):
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        return self.logits(params, params["W"], x), state
