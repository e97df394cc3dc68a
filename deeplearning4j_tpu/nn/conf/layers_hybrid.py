"""Pre-norm decoder blocks for hybrid state-space / attention stacks.

Two blocks, each ``x = x + Mixer(RMSNorm(x))`` then ``x = x +
SwiGLU(RMSNorm(x))``, no biases and no positional term:

* ``MambaBlockRun`` -- the mixer is a Mamba-1 selective state-space
  layer (input projection, causal depthwise convolution, input-dependent
  step size and B / C, a diagonal recurrence over a ``d_state``-wide
  state per channel, gated output projection; RMSNorm on dt, B and C).
* ``AttentionBlockRun`` -- the mixer is causal softmax attention with
  grouped query heads (``n_heads`` query heads on ``n_kv_heads`` K/V
  heads).

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
layer ``tied_to`` names, so no second table exists.
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

    def _ffn_params(self, keys, dtype):
        d, ff, n = self.n_in, self.d_ff, self.n_blocks
        return {"norm2": jnp.ones((n, d), dtype),
                "W_gate": self._matrix(keys[0], (d, ff), dtype),
                "W_up": self._matrix(keys[1], (d, ff), dtype),
                "W_down": self._matrix(keys[2], (ff, d), dtype)}

    def _ffn(self, p, x):
        n = rms_norm(x, p["norm2"], self.eps)
        w = lambda k: p[k].astype(x.dtype)
        return x + (_silu(n @ w("W_gate")) * (n @ w("W_up"))) @ w("W_down")

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None, mask=None):
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        y, _ = jax.lax.scan(
            lambda h, p: (self.sequence(p, h)[0], None), x, params)
        return y, state


@register_layer
@dataclasses.dataclass
class AttentionBlockRun(_PreNormRun):
    """``n_blocks`` x [grouped-query causal attention + SwiGLU].  No
    rotary or other positional term: in a hybrid stack the recurrent
    layers carry the order."""

    n_heads: int = 8
    n_kv_heads: int = 1
    head_dim: Optional[int] = None   # default d / n_heads

    #: why a server cannot share, restore, re-verify or shard this
    #: kind's K/V rows (None would mean it can)
    REFUSES = ("AttentionBlockRun layers: the run has no "
               "sequence(prefix=) over cached K/V rows, no W-row verify "
               "step() and no shard points yet (ROADMAP M1)")

    def _check_widths(self):
        if self.head_dim is None:
            if self.n_in % self.n_heads:
                raise ValueError(f"d_model {self.n_in} must divide by "
                                 f"n_heads {self.n_heads}")
            self.head_dim = self.n_in // self.n_heads
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads {self.n_heads} must divide by "
                             f"n_kv_heads {self.n_kv_heads}")

    def _init(self, key, dtype):
        d, n = self.n_in, self.n_blocks
        hq, hkv, dh = self.n_heads, self.n_kv_heads, self.head_dim
        ks = jax.random.split(key, 7)
        return {"norm1": jnp.ones((n, d), dtype),
                "Wq": self._matrix(ks[0], (d, hq * dh), dtype),
                "Wk": self._matrix(ks[1], (d, hkv * dh), dtype),
                "Wv": self._matrix(ks[2], (d, hkv * dh), dtype),
                "Wo": self._matrix(ks[3], (hq * dh, d), dtype),
                **self._ffn_params(ks[4:], dtype)}

    def _qkv(self, p, x):
        n = rms_norm(x, p["norm1"], self.eps)
        lead = x.shape[:-1]
        hq, hkv, dh = self.n_heads, self.n_kv_heads, self.head_dim
        return ((n @ p["Wq"].astype(x.dtype)).reshape(lead + (hq, dh)),
                (n @ p["Wk"].astype(x.dtype)).reshape(lead + (hkv, dh)),
                (n @ p["Wv"].astype(x.dtype)).reshape(lead + (hkv, dh)))

    def sequence(self, p, x, t0=None, shard=None):
        """x [b, t, d] -> (y, {"k", "v"} [b, n_kv_heads, t, head_dim],
        the rows as a pool holds them).  Causal, so ``t0`` (a padded
        prompt's real length) changes nothing a real position reads.
        No ``prefix=`` form and no shard points (``shard`` names a
        device, never a split): ``REFUSES``."""
        b, t, _ = x.shape
        hq, hkv, dh = self.n_heads, self.n_kv_heads, self.head_dim
        q, k, v = self._qkv(p, x)
        qg = q.reshape(b, t, hkv, hq // hkv, dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
        s = s * (1.0 / math.sqrt(dh))
        causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        s = jnp.where(causal[None, None, None], s, -1e9)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        att = jnp.einsum("bhgqk,bkhd->bqhgd", w, v).reshape(b, t, hq * dh)
        x = x + att @ p["Wo"].astype(x.dtype)
        return self._ffn(p, x), {"k": k.transpose(0, 2, 1, 3),
                                 "v": v.transpose(0, 2, 1, 3)}

    def step(self, p, x, attend, shard=None):
        """x [b, d], one new token per row.  ``attend(q [b, n_heads,
        head_dim], k, v [b, n_kv_heads, head_dim]) -> (att like q,
        cache)`` writes the row and reads the context."""
        q, k, v = self._qkv(p, x)
        att, cache = attend(q, k, v)
        x = x + att.reshape(x.shape[0], -1) @ p["Wo"].astype(x.dtype)
        return self._ffn(p, x), cache


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
        return self._ffn(p, x), {"h": h, "conv": win}

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
        return self._ffn(p, x), {"h": h, "conv": conv}


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
