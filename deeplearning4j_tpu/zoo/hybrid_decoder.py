"""Hybrid state-space / attention decoder LM.

A pre-norm decoder whose token mixers are mostly Mamba-1 selective
state-space layers, with a causal grouped-query attention layer every
``attn_period`` layers (at ``i % attn_period == attn_offset``); every
layer ends in a dense SwiGLU feed-forward, norms are RMSNorm, there
are no biases and no positional term (the recurrent layers carry the
order), and the LM head is the embedding table transposed.

The net is ``EmbeddingSequenceLayer -> runs -> TiedLMHead`` where a
run (``MambaBlockRun`` / ``AttentionBlockRun``) holds consecutive
layers of one kind with parameters stacked on a leading axis, so
``params_tree`` is the serving layout as ``init()`` makes it and a
``GenerationServer`` snapshot aliases it.  ``dtype`` is the
parameters' own dtype (``"bfloat16"`` for a served-only model: a
float32 ``init()`` of a 3B-parameter net is 12 GB).

Inference only: the head has no loss.  ``TransformerGenerator`` decodes
it offline (walking each run's ``sequence()`` and ``step()``) and
``GenerationServer`` serves it -- with per-slot recurrent state beside
the paged K/V pool, and without what its run kinds cannot do (their
``REFUSES``: prefix reuse, speculation, the host tier, prefill
hand-off, ``tp > 1``), which raises at construction -- for a stack of
attention runs alone too.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from deeplearning4j_tpu.nn.conf.builder import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers_hybrid import (AttentionBlockRun,
                                                      MambaBlockRun,
                                                      TiedLMHead)
from deeplearning4j_tpu.nn.conf.layers_transformer import \
    EmbeddingSequenceLayer
from deeplearning4j_tpu.zoo.base import ZooModel


@dataclasses.dataclass
class HybridDecoder(ZooModel):
    """``HybridDecoder()`` is a small 8-layer stack; every size is an
    argument."""

    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 8
    d_ff: int = 2048
    n_heads: int = 4
    n_kv_heads: int = 1
    head_dim: Optional[int] = None    # default d_model / n_heads
    attn_period: int = 4
    attn_offset: int = 2
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None     # default ceil(d_model / 16)
    eps: float = 1e-6
    seq_len: int = 512
    compute_dtype: Optional[str] = "bfloat16"
    dtype: str = "float32"            # the parameters' dtype

    def layer_kinds(self):
        """'attention' | 'mamba' for each of the ``n_layers``."""
        return ["attention" if i % self.attn_period == self.attn_offset
                else "mamba" for i in range(self.n_layers)]

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
        kinds = self.layer_kinds()
        i = 0
        while i < len(kinds):
            n = 1
            while i + n < len(kinds) and kinds[i + n] == kinds[i]:
                n += 1
            if kinds[i] == "mamba":
                lst = lst.layer(MambaBlockRun(
                    n_blocks=n, d_ff=self.d_ff, d_state=self.d_state,
                    d_conv=self.d_conv, expand=self.expand,
                    dt_rank=self.dt_rank, eps=self.eps))
            else:
                lst = lst.layer(AttentionBlockRun(
                    n_blocks=n, d_ff=self.d_ff, n_heads=self.n_heads,
                    n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
                    eps=self.eps))
            i += n
        return lst.layer(TiedLMHead(n_out=self.vocab_size,
                                    eps=self.eps)).build()
