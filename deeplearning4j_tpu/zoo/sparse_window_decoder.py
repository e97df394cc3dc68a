"""Sparse-expert decoder LM with window and full attention mixed.

A pre-norm decoder (RMSNorm, no biases) whose layers are causal
grouped-query attention blocks of two kinds, by a per-layer pattern:
FULL attention, and SLIDING-WINDOW attention with its own number of
K/V heads, its own rotary base and a learned sink logit per query
head.  Keys and queries are ``qk_dim`` wide and rotate on their first
``rotary_dim`` lanes; values are ``v_dim`` wide and scaled.  A layer's
feed-forward is a dense SwiGLU (``d_ff`` wide) or, by a second
per-layer list, a ROUTED one: a sigmoid router over ``n_experts``,
the ``top_k`` of score + bias, weights normalised, experts ``expert_ff``
wide -- of which this chip holds ``held`` = (first, count), as expert
parallelism deals them.  The head is an RMSNorm and its own matrix.

By further arguments, each of which leaves the stack as it was where it
is not given: a window layer may have its own number of query heads
(``window_heads``) and rotate its own number of lanes
(``window_rotary_dim``); either kind's rotary base may be scaled
(``rope_scaling`` / ``window_rope_scaling``: YaRN); a sigmoid ``gate``
a query head, read off the layer's normed input, may multiply the
attention's output; a routed layer may have a SHARED expert
``shared_ff`` wide that every token takes, and a factor
``routed_scale`` on the routed experts' weighted sum.

The net is ``EmbeddingSequenceLayer -> AttentionBlockRun ... ->
LMHead``: consecutive layers of one attention kind and one
feed-forward kind are one run (parameters stacked on a leading axis),
so ``params_tree`` is the serving layout as ``init()`` makes it.
``dtype`` is the parameters' own dtype (``"bfloat16"`` for a
served-only model).

Inference only: the head has no loss.  ``TransformerGenerator`` decodes
it offline and ``GenerationServer`` serves it -- the full layers over
the paged, allocated pool, each window layer over a ring of its
window's blocks (one or several) that a slot owns for life, the held
experts through
``kernels.expert_ffn`` -- without what its runs cannot do yet (their
``REFUSES``: prefix reuse, the host tier, ``export_prefix`` /
``import_blocks``, ``prefill_async``, speculation, ``tp > 1``), which
raises at construction.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf.builder import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers_hybrid import (AttentionBlockRun,
                                                      LMHead)
from deeplearning4j_tpu.nn.conf.layers_transformer import \
    EmbeddingSequenceLayer
from deeplearning4j_tpu.zoo.base import ZooModel


@dataclasses.dataclass
class SparseWindowDecoder(ZooModel):
    """``SparseWindowDecoder()`` is a small 7-layer stack (a dense full
    layer, then windows with a full layer among them, all routed);
    every size is an argument."""

    vocab_size: int = 32000
    d_model: int = 512
    #: 0 = full attention, 1 = window, a layer an entry
    layer_pattern: Sequence[int] = (0, 1, 1, 1, 1, 0, 1)
    #: 0 = dense SwiGLU, 1 = routed, a layer an entry
    routed_layers: Sequence[int] = (0, 1, 1, 1, 1, 1, 1)
    n_heads: int = 8
    n_kv_heads: int = 2               # of a full layer
    window_kv_heads: int = 4          # of a window layer
    qk_dim: int = 96
    v_dim: int = 64
    rotary_dim: int = 32
    rope_theta: float = 5e6           # full layers
    window_rope_theta: float = 1e4    # window layers
    value_scale: Optional[float] = 0.707
    window: int = 128
    window_sink: bool = True
    full_sink: bool = False
    d_ff: int = 2048                  # a dense layer's
    expert_ff: int = 256              # an expert's
    n_experts: int = 32
    top_k: int = 4
    held: Optional[Sequence[int]] = None   # (first, count); None: all
    window_heads: Optional[int] = None         # default: n_heads
    window_rotary_dim: Optional[int] = None    # default: rotary_dim
    rope_scaling: Optional[dict] = None        # full layers
    window_rope_scaling: Optional[dict] = None
    gate: bool = False                # sigmoid gate a query head
    shared_ff: Optional[int] = None   # a shared expert's width
    routed_scale: Optional[float] = None
    eps: float = 1e-5
    seq_len: int = 512
    compute_dtype: Optional[str] = "bfloat16"
    dtype: str = "float32"            # the parameters' dtype

    def runs(self):
        """[(window?, routed?, layers)]: consecutive layers of one
        attention kind and one feed-forward kind."""
        if len(self.layer_pattern) != len(self.routed_layers):
            raise ValueError("layer_pattern and routed_layers name "
                             f"{len(self.layer_pattern)} and "
                             f"{len(self.routed_layers)} layers")
        out = []
        for kind in zip(map(bool, self.layer_pattern),
                        map(bool, self.routed_layers)):
            if out and out[-1][:2] == kind:
                out[-1] = kind + (out[-1][2] + 1,)
            else:
                out.append(kind + (1,))
        return out

    def conf(self):
        b = (NeuralNetConfiguration.builder().seed(self.seed)
             .dtype(self.dtype)
             .weight_init("distribution", {"type": "normal",
                                           "mean": 0.0, "std": 0.02}))
        if self.compute_dtype:
            b = b.compute_dtype(self.compute_dtype)
        lst = (b.list()
               .set_input_type(InputType.feed_forward(self.seq_len))
               .layer(EmbeddingSequenceLayer(
                   n_in=self.vocab_size, n_out=self.d_model,
                   add_positional=False, layer_norm=False)))
        for windowed, routed, n in self.runs():
            ffn = (dict(d_ff=self.expert_ff, n_experts=self.n_experts,
                        top_k=self.top_k,
                        held=None if self.held is None else tuple(self.held),
                        shared_ff=self.shared_ff,
                        routed_scale=self.routed_scale)
                   if routed else dict(d_ff=self.d_ff))
            lst = lst.layer(AttentionBlockRun(
                n_blocks=n,
                n_heads=((self.window_heads or self.n_heads) if windowed
                         else self.n_heads),
                n_kv_heads=(self.window_kv_heads if windowed
                            else self.n_kv_heads),
                head_dim=self.qk_dim, qk_dim=self.qk_dim, v_dim=self.v_dim,
                rotary_dim=((self.window_rotary_dim or self.rotary_dim)
                            if windowed else self.rotary_dim),
                rope_theta=(self.window_rope_theta if windowed
                            else self.rope_theta),
                rope_scaling=(self.window_rope_scaling if windowed
                              else self.rope_scaling),
                value_scale=self.value_scale,
                window=self.window if windowed else None,
                sink=self.window_sink if windowed else self.full_sink,
                gate=self.gate, eps=self.eps, **ffn))
        return lst.layer(LMHead(n_out=self.vocab_size,
                                eps=self.eps)).build()
