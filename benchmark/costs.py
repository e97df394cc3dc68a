"""Operations and bytes from shapes: what the algorithm needs, not what
a program happens to execute (recomputation does not count).  ``shape``
is a configuration's ``shape`` group: d, layers, heads, ff, vocab, n_out.
Kept with the benchmark so that no PR that claims a gain can move it.
"""
from __future__ import annotations


def matmul_params(shape: dict) -> int:
    """Parameters of the blocks' matrix products (no tables, no head)."""
    d, ff = shape["d"], shape["ff"]
    return shape["layers"] * (4 * d * d + 2 * d * ff)


def train_flops_per_token(shape: dict, seq: int) -> float:
    """Forward + backward of the encoder classifier: 6 per matmul
    parameter (2 forward, 4 backward) and full attention's two products
    (4 * seq * d forward per token and layer, x3)."""
    return 6.0 * matmul_params(shape) + 12.0 * shape["layers"] * seq * shape["d"]


def lm_forward_flops(shape: dict, first_ctx: int, last_ctx: int,
                     heads_out: int) -> float:
    """Forward operations of a causal decoder over the tokens whose
    context lengths (keys attended, itself included) run from
    ``first_ctx`` to ``last_ctx``; the LM head is applied to
    ``heads_out`` of them."""
    n = last_ctx - first_ctx + 1
    if n <= 0:
        return 0.0
    ctx_sum = (first_ctx + last_ctx) * n / 2.0
    return (2.0 * matmul_params(shape) * n
            + 4.0 * shape["layers"] * shape["d"] * ctx_sum
            + 2.0 * shape["d"] * shape["n_out"] * heads_out)


def flash_cost(shape: dict, batch: int, seq: int, causal: bool,
               backward: bool, itemsize: int = 2) -> dict:
    """One flash-attention call over [batch, seq, heads, d/heads]:
    forward is QK^T and PV; backward is the four products that dV, dP,
    dQ and dK need (the recomputed scores do not count).  Bytes: q, k,
    v, o once each forward; those and their four gradients backward."""
    d = shape["d"]
    products = 4 if backward else 2
    flops = products * 2.0 * batch * seq * seq * d * (0.5 if causal else 1.0)
    tensors = 8 if backward else 4
    return {"flops": flops, "bytes": float(tensors * batch * seq * d * itemsize)}


def paged_attention_cost(shape: dict, ctx_sum: float, itemsize: int = 2) -> dict:
    """Decode attention over the paged pool, all layers: every
    generated token reads the K and V of its live context once per
    layer (``ctx_sum`` = the sum of those context lengths over the
    tokens generated) -- the live KV, not the pool."""
    L, d = shape["layers"], shape["d"]
    return {"flops": 4.0 * L * d * ctx_sum,
            "bytes": 2.0 * L * d * itemsize * ctx_sum}


def roofline_seconds(cost: dict, peak: dict) -> tuple:
    """The least time the chip could take, and which limit sets it."""
    by_flops = cost["flops"] / peak["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return ((by_flops, "compute") if by_flops >= by_bytes
            else (by_bytes, "bandwidth"))
