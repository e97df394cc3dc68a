"""Remaining DL4J layer types (round-1 review item 8).

Parity targets (``org.deeplearning4j.nn.conf.layers.**``):
``PReLULayer``, ``ElementWiseMultiplicationLayer``,
``LocallyConnected1D``/``LocallyConnected2D``, ``SelfAttentionLayer`` /
``LearnedSelfAttentionLayer``, ``Convolution3D`` / ``Subsampling3D``,
``CenterLossOutputLayer``, ``variational.VariationalAutoencoder``.

TPU notes: locally-connected layers extract patches with
``lax.conv_general_dilated_patches`` and contract with one einsum (no
per-position loop); attention is batched einsum softmax einsum — the MXU
path (a Pallas flash kernel can swap in later without touching configs);
3-D conv uses XLA's NDHWC lowering directly.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.conf.base import BaseLayerConf, register_layer
from deeplearning4j_tpu.nn.conf.layers_conv import _pair
from deeplearning4j_tpu.nn.conf.layers_core import (
    BaseOutputLayerConf, DenseLayer, apply_dropout)
from deeplearning4j_tpu.nn.weights_init import init_weights


def _triple(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in (list(v) + [v[-1]] * 3)[:3])
    return (int(v),) * 3


# ---------------------------------------------------------------------------
@register_layer
@dataclasses.dataclass
class PReLULayer(BaseLayerConf):
    """Parametric ReLU (``PReLULayer``): one learned alpha per input
    element, optionally shared over axes (DL4J ``sharedAxes``, 1-indexed
    over non-batch dims as upstream)."""

    input_shape: Optional[Sequence[int]] = None  # inferred
    shared_axes: Optional[Sequence[int]] = None

    WANTED_KINDS = ("ff", "cnn", "rnn")

    def infer_shapes(self, input_shape):
        shape = list(input_shape)
        for ax in (self.shared_axes or ()):
            shape[int(ax) - 1] = 1  # DL4J sharedAxes are 1-indexed
        for i, d in enumerate(shape):
            if d is None:
                raise ValueError(
                    "PReLULayer needs every non-shared input dim fixed; "
                    f"dim {i + 1} is dynamic — add it to shared_axes or "
                    "use a fixed InputType (e.g. recurrent(size, "
                    "timesteps))")
        # Dynamic dims are legal only on shared axes (alpha dim 1 there).
        self.input_shape = tuple(
            int(d) if d is not None else None for d in input_shape)
        self._alpha_shape = tuple(int(d) for d in shape)
        return input_shape

    def has_params(self):
        return True

    def init(self, key, dtype=jnp.float32):
        return {"alpha": jnp.zeros(self._alpha_shape, dtype)}, {}

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None):
        a = params["alpha"].astype(x.dtype)
        y = jnp.maximum(x, 0) + a * jnp.minimum(x, 0)
        return apply_dropout(y, self.dropout, training, rng), state


# ---------------------------------------------------------------------------
@register_layer
@dataclasses.dataclass
class ElementWiseMultiplicationLayer(BaseLayerConf):
    """y = act(x * w + b) with learned per-feature w, b
    (``ElementWiseMultiplicationLayer``); n_out == n_in."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None

    WANTED_KINDS = ("ff",)

    def infer_shapes(self, input_shape):
        f = int(input_shape[-1])
        if self.n_out is not None and self.n_out != f:
            # DL4J validates nIn == nOut and fails fast.
            raise ValueError(
                f"ElementWiseMultiplicationLayer requires n_out == n_in "
                f"(got n_out={self.n_out}, input width {f})")
        self.n_in = self.n_out = f
        return input_shape

    def has_params(self):
        return True

    def init(self, key, dtype=jnp.float32):
        return {"w": jnp.ones((self.n_in,), dtype),
                "b": jnp.zeros((self.n_in,), dtype)}, {}

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None):
        y = x * params["w"].astype(x.dtype) + params["b"].astype(x.dtype)
        y = get_activation(self.activation or "identity")(y)
        return apply_dropout(y, self.dropout, training, rng), state


# ---------------------------------------------------------------------------
@register_layer
@dataclasses.dataclass
class LocallyConnected2D(BaseLayerConf):
    """Unshared 2-D convolution (``LocallyConnected2D``): a separate
    kernel per output position.  Patches come from one
    ``conv_general_dilated_patches`` call; the per-position contraction is
    a single einsum the MXU batches over positions."""

    kernel_size: Sequence[int] = (2, 2)
    stride: Sequence[int] = (1, 1)
    convolution_mode: str = "truncate"  # or 'same'
    n_in: Optional[int] = None
    n_out: Optional[int] = None
    has_bias: bool = True
    _out_hw: Optional[Tuple[int, int]] = None

    WANTED_KINDS = ("cnn",)

    def _padding(self):
        return "SAME" if self.convolution_mode == "same" else "VALID"

    def infer_shapes(self, input_shape):
        h, w, c = (int(d) for d in input_shape)
        self.n_in = c
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        if self.convolution_mode == "same":
            oh, ow = -(-h // sh), -(-w // sw)
        else:
            oh, ow = (h - kh) // sh + 1, (w - kw) // sw + 1
        self._out_hw = (oh, ow)
        return (oh, ow, self.n_out)

    def has_params(self):
        return True

    def init(self, key, dtype=jnp.float32):
        kh, kw = _pair(self.kernel_size)
        oh, ow = self._out_hw
        fan_in = self.n_in * kh * kw
        w = init_weights(key, (oh, ow, kh * kw * self.n_in, self.n_out),
                         fan_in, self.n_out, self.weight_init, dtype,
                         self.weight_distribution)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.full((oh, ow, self.n_out), self.bias_init,
                                   dtype)
        return params, {}

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None):
        w = params["W"]
        if compute_dtype is not None:
            x, w = x.astype(compute_dtype), w.astype(compute_dtype)
        patches = lax.conv_general_dilated_patches(
            x, _pair(self.kernel_size), _pair(self.stride), self._padding(),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        # conv_general_dilated_patches emits the patch feature dim as
        # C*kh*kw with the INPUT CHANNEL major (spatial offsets minor);
        # W's [oh, ow, kh*kw*cin, cout] dim 2 uses the same order.  Any
        # future weight importer for locally-connected layers must
        # permute into this layout.
        y = jnp.einsum("bhwk,hwko->bhwo", patches, w)
        if self.has_bias:
            y = y + params["b"].astype(y.dtype)
        y = get_activation(self.activation or "identity")(y)
        return apply_dropout(y, self.dropout, training, rng), state


@register_layer
@dataclasses.dataclass
class LocallyConnected1D(BaseLayerConf):
    """Unshared 1-D convolution over [b, t, f] (``LocallyConnected1D``)."""

    kernel_size: int = 2
    stride: int = 1
    convolution_mode: str = "truncate"
    n_in: Optional[int] = None
    n_out: Optional[int] = None
    has_bias: bool = True
    _out_t: Optional[int] = None

    WANTED_KINDS = ("rnn",)
    IS_RNN = False

    def infer_shapes(self, input_shape):
        t, f = input_shape
        self.n_in = int(f)
        k, s = int(self.kernel_size), int(self.stride)
        if self.convolution_mode == "same":
            ot = -(-int(t) // s) if t is not None else None
        else:
            ot = (int(t) - k) // s + 1 if t is not None else None
        self._out_t = ot
        return (ot, self.n_out)

    def has_params(self):
        return True

    def init(self, key, dtype=jnp.float32):
        k = int(self.kernel_size)
        ot = self._out_t
        if ot is None:
            raise ValueError(
                "LocallyConnected1D needs a fixed sequence length "
                "(InputType.recurrent(size, timesteps))")
        fan_in = self.n_in * k
        w = init_weights(key, (ot, k * self.n_in, self.n_out), fan_in,
                         self.n_out, self.weight_init, dtype,
                         self.weight_distribution)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.full((ot, self.n_out), self.bias_init, dtype)
        return params, {}

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None):
        w = params["W"]
        if compute_dtype is not None:
            x, w = x.astype(compute_dtype), w.astype(compute_dtype)
        pad = "SAME" if self.convolution_mode == "same" else "VALID"
        patches = lax.conv_general_dilated_patches(
            x, (int(self.kernel_size),), (int(self.stride),), pad,
            dimension_numbers=("NTC", "TIO", "NTC"))
        y = jnp.einsum("btk,tko->bto", patches, w)
        if self.has_bias:
            y = y + params["b"].astype(y.dtype)
        y = get_activation(self.activation or "identity")(y)
        return apply_dropout(y, self.dropout, training, rng), state


# ---------------------------------------------------------------------------
@register_layer
@dataclasses.dataclass
class SelfAttentionLayer(BaseLayerConf):
    """Multi-head dot-product self-attention over [b, t, f]
    (``SelfAttentionLayer``): n_heads x head_size projections, optional
    output projection (``projectInput``), feature-mask aware."""

    n_heads: int = 1
    head_size: Optional[int] = None
    project_input: bool = True
    n_in: Optional[int] = None
    n_out: Optional[int] = None
    # Route the unmasked path through the Pallas flash kernel (TPU; CPU
    # uses its interpret mode).  Falls back to the einsum path whenever
    # a mask is present or the sequence doesn't tile.
    use_flash: bool = False

    WANTED_KINDS = ("rnn",)
    USES_MASK = True

    def infer_shapes(self, input_shape):
        t, f = input_shape
        self.n_in = int(f)
        if self.head_size is None:
            self.head_size = self.n_in // self.n_heads
        d = self.n_heads * self.head_size
        if not self.project_input and d != self.n_in:
            # DL4J SelfAttentionLayer validates exactly this.
            raise ValueError(
                f"projectInput=false requires n_heads*head_size == n_in "
                f"({self.n_heads}x{self.head_size} != {self.n_in})")
        if self.n_out is None:
            self.n_out = d if self.project_input else self.n_in
        return (t, self.n_out)

    def has_params(self):
        return True

    def init(self, key, dtype=jnp.float32):
        d = self.n_heads * self.head_size
        ks = jax.random.split(key, 4)
        mk = lambda k, shape: init_weights(k, shape, shape[0], shape[-1],
                                           self.weight_init, dtype,
                                           self.weight_distribution)
        params = {"Wq": mk(ks[0], (self.n_in, d)),
                  "Wk": mk(ks[1], (self.n_in, d)),
                  "Wv": mk(ks[2], (self.n_in, d))}
        if self.project_input:
            params["Wo"] = mk(ks[3], (d, self.n_out))
        return params, {}

    def _attend(self, q, k, v, mask):
        h, s = self.n_heads, self.head_size
        b, t, _ = q.shape
        split = lambda z: z.reshape(b, -1, h, s).transpose(0, 2, 1, 3)
        q, k, v = split(q), split(k), split(v)
        # The fused-attention entry routes to the Pallas flash kernel
        # when the shape permits (auto-tuned blocks) and falls back to
        # the XLA einsum path otherwise; a [b, t] sequence mask becomes
        # the kernel's additive key-position bias.
        if self.use_flash and q.shape[2] == k.shape[2]:
            from deeplearning4j_tpu.kernels import attention, mask_to_bias
            bias = mask_to_bias(mask)
            if jax.default_backend() == "tpu" and q.dtype == jnp.float32:
                # f32 operands run the MXU at 1/8 rate (see the
                # kernel header): use_flash on TPU implies bf16
                # attention math, the TPU-native training choice.
                q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
            out = attention(q, k, v, bias=bias)
            return out.transpose(0, 2, 1, 3).reshape(b, -1, h * s)
        logits = jnp.einsum("bhqs,bhks->bhqk", q, k) / jnp.sqrt(
            jnp.asarray(s, q.dtype))
        if mask is not None:
            neg = jnp.asarray(-1e9, logits.dtype)
            logits = jnp.where(mask[:, None, None, :] > 0, logits, neg)
        att = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bhks->bhqs", att, v)
        return out.transpose(0, 2, 1, 3).reshape(b, -1, h * s)

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None, mask=None):
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        cast = lambda w: w.astype(x.dtype)
        q = x @ cast(params["Wq"])
        k = x @ cast(params["Wk"])
        v = x @ cast(params["Wv"])
        y = self._attend(q, k, v, mask)
        if self.project_input:
            y = y @ cast(params["Wo"])
        return apply_dropout(y, self.dropout, training, rng), state


@register_layer
@dataclasses.dataclass
class LearnedSelfAttentionLayer(SelfAttentionLayer):
    """Attention with LEARNED queries (``LearnedSelfAttentionLayer``):
    n_queries fixed query vectors attend over the sequence; output is
    [b, n_queries, n_out] regardless of input length."""

    n_queries: int = 1

    def infer_shapes(self, input_shape):
        t, f = input_shape
        super().infer_shapes((t, f))
        return (self.n_queries, self.n_out)

    def init(self, key, dtype=jnp.float32):
        kq, rest = jax.random.split(key)
        params, state = super().init(rest, dtype)
        del params["Wq"]
        d = self.n_heads * self.head_size
        params["Q"] = init_weights(kq, (self.n_queries, d), d, d,
                                   self.weight_init, dtype,
                                   self.weight_distribution)
        return params, state

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None, mask=None):
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        cast = lambda w: w.astype(x.dtype)
        b = x.shape[0]
        q = jnp.broadcast_to(cast(params["Q"])[None],
                             (b,) + params["Q"].shape)
        k = x @ cast(params["Wk"])
        v = x @ cast(params["Wv"])
        y = self._attend(q, k, v, mask)
        if self.project_input:
            y = y @ cast(params["Wo"])
        return apply_dropout(y, self.dropout, training, rng), state


# ---------------------------------------------------------------------------
@register_layer
@dataclasses.dataclass
class Convolution3D(BaseLayerConf):
    """3-D convolution over [b, d, h, w, c] (``Convolution3D``, NDHWC —
    DL4J's NDHWC option; XLA lowers this natively)."""

    kernel_size: Sequence[int] = (2, 2, 2)
    stride: Sequence[int] = (1, 1, 1)
    convolution_mode: str = "truncate"
    n_in: Optional[int] = None
    n_out: Optional[int] = None
    has_bias: bool = True

    WANTED_KINDS = ("cnn3d",)

    def _padding(self):
        return "SAME" if self.convolution_mode == "same" else "VALID"

    def infer_shapes(self, input_shape):
        d, h, w, c = (int(v) for v in input_shape)
        self.n_in = c
        kd, kh, kw = _triple(self.kernel_size)
        sd, sh, sw = _triple(self.stride)
        if self.convolution_mode == "same":
            od, oh, ow = -(-d // sd), -(-h // sh), -(-w // sw)
        else:
            od, oh, ow = ((d - kd) // sd + 1, (h - kh) // sh + 1,
                          (w - kw) // sw + 1)
        return (od, oh, ow, self.n_out)

    def has_params(self):
        return True

    def init(self, key, dtype=jnp.float32):
        kd, kh, kw = _triple(self.kernel_size)
        fan_in = self.n_in * kd * kh * kw
        w = init_weights(key, (kd, kh, kw, self.n_in, self.n_out), fan_in,
                         self.n_out, self.weight_init, dtype,
                         self.weight_distribution)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params, {}

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None):
        w = params["W"]
        if compute_dtype is not None:
            x, w = x.astype(compute_dtype), w.astype(compute_dtype)
        z = lax.conv_general_dilated(
            x, w, window_strides=_triple(self.stride),
            padding=self._padding(),
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
        if self.has_bias:
            z = z + params["b"].astype(z.dtype)
        y = get_activation(self.activation or "identity")(z)
        return apply_dropout(y, self.dropout, training, rng), state


@register_layer
@dataclasses.dataclass
class Subsampling3DLayer(BaseLayerConf):
    """3-D max/avg pooling (``Subsampling3DLayer``)."""

    kernel_size: Sequence[int] = (2, 2, 2)
    stride: Sequence[int] = (2, 2, 2)
    pooling_type: str = "max"

    WANTED_KINDS = ("cnn3d",)

    def infer_shapes(self, input_shape):
        d, h, w, c = (int(v) for v in input_shape)
        kd, kh, kw = _triple(self.kernel_size)
        sd, sh, sw = _triple(self.stride)
        return ((d - kd) // sd + 1, (h - kh) // sh + 1, (w - kw) // sw + 1,
                c)

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None):
        k = (1,) + _triple(self.kernel_size) + (1,)
        s = (1,) + _triple(self.stride) + (1,)
        if self.pooling_type == "max":
            y = lax.reduce_window(x, -jnp.inf, lax.max, k, s, "VALID")
        elif self.pooling_type == "avg":
            tot = lax.reduce_window(x, 0.0, lax.add, k, s, "VALID")
            y = tot / float(math.prod(_triple(self.kernel_size)))
        else:
            raise ValueError(f"pooling_type {self.pooling_type!r}")
        return y, state


# ---------------------------------------------------------------------------
@register_layer
@dataclasses.dataclass
class CenterLossOutputLayer(BaseOutputLayerConf, DenseLayer):
    """Softmax head + center loss (``CenterLossOutputLayer``):
    L = CE + (lambda/2)·||f − c_y||².  Deviation from DL4J noted: centers
    are PARAMETERS optimized by the configured updater via the gradient
    of the center term (DL4J hand-applies an `alpha` moving average inside
    backprop); same fixed point, and the gradient-check harness covers
    the whole loss including the centers."""

    alpha: float = 0.05  # kept for config parity / serialization
    lambda_: float = 2e-4

    def init(self, key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        params, state = DenseLayer.init(self, k1, dtype)
        params["centers"] = jnp.zeros((self.n_out, self.n_in), dtype)
        return params, state

    def regularized_param_names(self):
        return ("W",)

    def center_score(self, params, features, labels):
        """(lambda/2)·||f − c_y||² per example; labels one-hot [b, C]."""
        centers_y = labels.astype(features.dtype) @ params["centers"].astype(
            features.dtype)
        return 0.5 * self.lambda_ * jnp.sum(
            jnp.square(features - centers_y), axis=-1)
    def per_example_score(self, labels, z, mask=None, head_input=None,
                          rng=None, params=None):
        ce = super().per_example_score(labels, z, mask)
        if head_input is None or params is None:
            return ce
        center = self.center_score(params, self.promote_head(head_input),
                                   labels)
        if mask is not None:
            center = center * mask.reshape(center.shape[0])
        return ce + center


# ---------------------------------------------------------------------------
@register_layer
@dataclasses.dataclass
class VariationalAutoencoder(BaseOutputLayerConf):
    """``variational.VariationalAutoencoder``: encoder MLP → (mu, logvar)
    → reparameterized z → decoder MLP → reconstruction distribution;
    trained on -ELBO with ``fit(DataSet(x, x))`` (DL4J trains it as the
    unsupervised pretrain layer).  ``apply`` returns the posterior MEAN
    (the embedding DL4J's activate() exposes).

    ``reconstruction_distribution``: 'gaussian' (loss over mean+logvar
    outputs) or 'bernoulli' (logits + binary CE).
    """

    n_in: Optional[int] = None
    n_out: Optional[int] = None  # latent size n_z
    encoder_layer_sizes: Sequence[int] = (16,)
    decoder_layer_sizes: Sequence[int] = (16,)
    reconstruction_distribution: str = "gaussian"
    num_samples: int = 1

    WANTED_KINDS = ("ff",)

    def infer_shapes(self, input_shape):
        self.n_in = int(input_shape[-1])
        return (self.n_out,)

    def has_params(self):
        return True

    def _stack_sizes(self):
        enc = [self.n_in, *self.encoder_layer_sizes]
        dec = [self.n_out, *self.decoder_layer_sizes]
        recon_out = (2 * self.n_in
                     if self.reconstruction_distribution == "gaussian"
                     else self.n_in)
        return enc, dec, recon_out

    def init(self, key, dtype=jnp.float32):
        enc, dec, recon_out = self._stack_sizes()
        n_mats = (len(enc) - 1) + 2 + (len(dec) - 1) + 1
        ks = list(jax.random.split(key, n_mats))
        params = {}

        def dense(name, n_in, n_out):
            k = ks.pop(0)
            params[f"{name}_W"] = init_weights(
                k, (n_in, n_out), n_in, n_out, self.weight_init, dtype,
                self.weight_distribution)
            params[f"{name}_b"] = jnp.zeros((n_out,), dtype)

        for i in range(len(enc) - 1):
            dense(f"enc{i}", enc[i], enc[i + 1])
        dense("mu", enc[-1], self.n_out)
        dense("logvar", enc[-1], self.n_out)
        for i in range(len(dec) - 1):
            dense(f"dec{i}", dec[i], dec[i + 1])
        dense("recon", dec[-1], recon_out)
        return params, {}

    def _dense(self, params, name, x, act="relu"):
        y = x @ params[f"{name}_W"].astype(x.dtype) + \
            params[f"{name}_b"].astype(x.dtype)
        return get_activation(act)(y)

    def _encode(self, params, x):
        h = x
        for i in range(len(self.encoder_layer_sizes)):
            h = self._dense(params, f"enc{i}", h,
                            self.activation or "relu")
        mu = self._dense(params, "mu", h, "identity")
        logvar = self._dense(params, "logvar", h, "identity")
        return mu, logvar

    def _decode(self, params, z):
        h = z
        for i in range(len(self.decoder_layer_sizes)):
            h = self._dense(params, f"dec{i}", h,
                            self.activation or "relu")
        return self._dense(params, "recon", h, "identity")

    def pre_output(self, params, x, compute_dtype=None):
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        return x

    def per_example_score(self, labels, z, mask=None, head_input=None,
                          rng=None, params=None):
        """-ELBO per example.  ``z`` is the raw feature batch (see
        pre_output); ``labels`` is the reconstruction target (DataSet(x,
        x) — DL4J ignores labels entirely and reconstructs the features;
        accepting a distinct target is a superset)."""
        if params is None:
            raise ValueError(
                "VariationalAutoencoder scoring needs the layer params "
                "(the model passes params= automatically)")
        x = self.promote_head(z)
        target = self.promote_head(labels) if labels is not None else x
        mu, logvar = self._encode(params, x)
        n_s = max(int(self.num_samples), 1)
        if rng is not None and self.num_samples > 0:
            # DL4J numSamples: Monte-Carlo average of the reconstruction
            # term over n_s reparameterized draws.
            eps = jax.random.normal(rng, (n_s,) + mu.shape, mu.dtype)
        else:
            eps = jnp.zeros((1,) + mu.shape, mu.dtype)  # mean-field path

        def recon_nll(e):
            zs = mu + e * jnp.exp(0.5 * logvar)
            out = self._decode(params, zs)
            if self.reconstruction_distribution == "gaussian":
                r_mu, r_logvar = jnp.split(out, 2, axis=-1)
                return 0.5 * jnp.sum(
                    r_logvar + jnp.square(target - r_mu) / jnp.exp(r_logvar)
                    + jnp.log(2 * jnp.pi), axis=-1)
            if self.reconstruction_distribution == "bernoulli":
                # softplus form: stable for large |logit| (exp(-out)
                # overflows f32 past ~88)
                return jnp.sum(jax.nn.softplus(out) - out * target,
                               axis=-1)
            raise ValueError(self.reconstruction_distribution)

        nll = jnp.mean(jax.vmap(recon_nll)(eps), axis=0)
        kl = -0.5 * jnp.sum(1 + logvar - jnp.square(mu) - jnp.exp(logvar),
                            axis=-1)
        score = nll + kl
        if mask is not None:
            score = score * mask.reshape(score.shape[0])
        return score

    def apply(self, params, state, x, *, training: bool, rng=None,
              compute_dtype=None):
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        mu, _ = self._encode(params, x)
        return self.promote_head(mu), state

    def reconstruct(self, params, x):
        """Encoder mean → decoder output (DL4J ``reconstructionOutput``)."""
        mu, _ = self._encode(params, jnp.asarray(x))
        out = self._decode(params, mu)
        if self.reconstruction_distribution == "gaussian":
            return jnp.split(out, 2, axis=-1)[0]
        return jax.nn.sigmoid(out)
