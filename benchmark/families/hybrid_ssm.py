"""The hybrid state-space / attention decoder family: pre-norm RMSNorm
blocks whose mixers are Mamba-1 layers with a grouped-query attention
layer every ``attn_period``, dense SwiGLU feed-forwards, a tied head
(``hybrid_ssm_reference.py`` writes the equations out).  It gives what
``post_ln.REQUIRED`` lists; it has no training reference, so a train
cell of it fails with the harness's plain message.

**The tree is lazy.**  At the published widths the float32 tree is
12 GB and ``drivers.seed_tree`` rounds a tree leaf by leaf while the
unrounded one is alive.  So a leaf here holds a key, a shape and a
recipe, answers ``astype`` by recording the rounding, and is drawn
where it is used: all at once under ``jit`` by ``to_program`` (a layer
at a time, each rounded before the next is drawn), and a layer at a
time by the reference pass, which then holds one layer's weights and
one sequence's activations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import reference
from benchmark.families import hybrid_ssm_reference as ref

#: one precision below the configuration on both counts: float8 matmul
#: operands, and the recurrent state carried in bfloat16
CONTROL = "fp8+state_bf16"
seed_key = reference.seed_key
weights_from_key = ref.weights_from_key
#: program layout <- paper layout, for the two leaves that differ
_TRANSPOSED = ("conv_w", "A_log")


def shape_of(config: dict) -> dict:
    """From the zoo class's constructor arguments.  ``layers`` keeps
    its name: ``ssm_tick_device_ms`` divides by it."""
    c = config["ctor"]
    kinds = ["attn" if i % c["attn_period"] == c["attn_offset"] else "mamba"
             for i in range(c["n_layers"])]
    d = c["d_model"]
    return {**({"init_std": config["init_std"]} if "init_std" in config else {}),
            "d": d, "layers": c["n_layers"],
            "ssm_layers": kinds.count("mamba"),
            "attn_layers": kinds.count("attn"),
            "attn_period": c["attn_period"], "attn_offset": c["attn_offset"],
            "heads": c["n_heads"], "kv_heads": c["n_kv_heads"],
            "head_dim": c.get("head_dim") or d // c["n_heads"],
            "ff": c["d_ff"], "vocab": c["vocab_size"],
            "d_state": c["d_state"], "d_conv": c["d_conv"],
            "d_inner": c["expand"] * d,
            "dt_rank": c.get("dt_rank") or -(-d // 16)}


def layout_of(net) -> tuple:
    """("emb",) | ("mamba" | "attn", first layer of its kind, blocks) |
    ("head",) for each layer of the net: a run of the program holds
    consecutive layers of one kind."""
    out, seen = [], {"mamba": 0, "attn": 0}
    for i, ly in enumerate(net.layers):
        if i == 0:
            out.append(("emb",))
        elif i == len(net.layers) - 1:
            out.append(("head",))
        else:
            kind = "mamba" if ly.RECURRENT else "attn"
            out.append((kind, seen[kind], ly.n_blocks))
            seen[kind] += ly.n_blocks
    return tuple(out)


def to_program(w, layout):
    """The program's ``params_tree``: every lazy leaf drawn (and
    rounded as its ``astype`` recorded), a run's leaves stacked on the
    leading axis the program scans."""
    turn = lambda name, a: a.T if name in _TRANSPOSED else a
    tree = {}
    for i, entry in enumerate(layout):
        if len(entry) == 1:
            tree[f"layer_{i}"] = {k: leaf.whole() for k, leaf in w[entry[0]].items()}
        else:
            kind, lo, n = entry
            tree[f"layer_{i}"] = ref.stack_layers(w[kind], lo, n, turn)
    return tree


def from_program(tree, layout):
    """Back to the reference's tree, as arrays."""
    out = {"emb": tree["layer_0"], "head": tree[f"layer_{len(layout) - 1}"]}
    for kind in ("mamba", "attn"):
        runs = [tree[f"layer_{i}"] for i, e in enumerate(layout) if e[0] == kind]
        out[kind] = {k: jnp.concatenate(
            [jnp.swapaxes(r[k], 1, 2) if k in _TRANSPOSED else r[k]
             for r in runs]) for k in runs[0]}
    return out


def leaf_norms(tree) -> dict:
    """One norm per parameter (stacked leaves layer by layer) of a
    reference-shaped tree, lazy or not."""
    out = {}
    for group, leaves in tree.items():
        for name, a in leaves.items():
            a = a.whole() if isinstance(a, ref.Leaf) else a
            axes = tuple(range(1, a.ndim)) if group in ("mamba", "attn") else None
            out[f"{group}.{name}"] = jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32)), axis=axes))
    return out


def served_token_gaps(w, shape: dict, seq, t0: int, quant=None):
    return ref.served_token_gaps(w, shape, seq, t0, quant)


# ---------------------------------------------------------------------------
# costs: what the algorithm needs, from shapes
# ---------------------------------------------------------------------------
def matmul_params(shape: dict) -> int:
    """Parameters of the layers' matrix products (no table, no head)."""
    d, di, ff = shape["d"], shape["d_inner"], shape["ff"]
    r, ns = shape["dt_rank"], shape["d_state"]
    hq, hkv, dh = shape["heads"], shape["kv_heads"], shape["head_dim"]
    mamba = d * 2 * di + di * (r + 2 * ns) + r * di + di * d
    attn = 2 * d * hq * dh + 2 * d * hkv * dh
    return (shape["ssm_layers"] * mamba + shape["attn_layers"] * attn
            + shape["layers"] * 3 * d * ff)


#: the state update of one token in one Mamba layer, per state element:
#: delta x A, exp, x h, delta*u x B, +, x C, + (and the skip and gate)
SSM_FLOPS_PER_STATE = 9.0


def _forward_flops(shape, first_ctx, last_ctx, heads_out) -> float:
    """Forward operations over the tokens whose context lengths run
    from ``first_ctx`` to ``last_ctx``; the head applied to
    ``heads_out`` of them."""
    n = last_ctx - first_ctx + 1
    if n <= 0:
        return 0.0
    ctx_sum = (first_ctx + last_ctx) * n / 2.0
    return (2.0 * matmul_params(shape) * n
            + 4.0 * shape["attn_layers"] * shape["heads"] * shape["head_dim"] * ctx_sum
            + SSM_FLOPS_PER_STATE * shape["ssm_layers"] * shape["d_inner"]
            * shape["d_state"] * n
            + 2.0 * shape["d"] * shape["vocab"] * heads_out)


def serve_work(shape: dict, triples) -> dict:
    """Forward operations; the attention kernel's context sum (token j
    of a request attends its prompt's t0 keys and j - 1 more); the
    decode ticks' tokens, each one state update in every Mamba layer."""
    flops = ctx_sum = decode_tokens = 0.0
    for t0, lo, hi in triples:
        if lo == 1:                     # the prefill made token 1
            flops += _forward_flops(shape, 1, t0, 1)
            lo = 2
        if hi >= lo:
            n = hi - lo + 1
            flops += _forward_flops(shape, t0 + lo - 1, t0 + hi - 1, n)
            ctx_sum += (2 * t0 + lo + hi - 2) * n / 2.0
            decode_tokens += n
    return {"flops": flops, "ctx_sum": ctx_sum, "decode_tokens": decode_tokens}


def train_flops_per_token(shape: dict, seq: int) -> float:
    """Forward + backward, were it trained: 6 per matmul parameter and
    three times the forward's attention and state update."""
    return 3.0 * _forward_flops(shape, seq / 2.0, seq / 2.0, 1)


def _paged_attention(shape, facts, traffic, events, args, itemsize: int = 2):
    """Every generated token reads the K and V of its live context once
    per attention layer, ``kv_heads`` rows of ``head_dim``."""
    kv = shape["attn_layers"] * shape["kv_heads"] * shape["head_dim"]
    return {"flops": 4.0 * shape["attn_layers"] * shape["heads"]
            * shape["head_dim"] * facts["ctx_sum"],
            "bytes": 2.0 * kv * itemsize * facts["ctx_sum"]}


def _ssm_step(shape, facts, traffic, events, args):
    """Per decode token and Mamba layer: the float32 state read and
    written, and the rows the update takes and gives -- u, z and the
    gated output in bfloat16, dt in float32, B and C.  (The convolution's
    window is shifted outside the kernel: not counted.)"""
    di, ns = shape["d_inner"], shape["d_state"]
    calls = facts["decode_tokens"] * shape["ssm_layers"]
    return {"flops": SSM_FLOPS_PER_STATE * di * ns * calls,
            "bytes": (2.0 * di * ns * 4 + di * (2 + 2 + 2 + 4) + 2 * ns * 4)
            * calls}


KERNEL_COSTS = {"paged_attention": _paged_attention, "ssm_step": _ssm_step}
