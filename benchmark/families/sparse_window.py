"""The sparse-expert decoder family with window and full attention
mixed: pre-norm RMSNorm blocks of grouped-query attention -- full or
sliding-window by a per-layer pattern, keys wider than values, a rotary
term on part of the head, a sink logit on the window layers -- with a
dense SwiGLU or a routed feed-forward of which this chip holds a share,
an untied head (``sparse_window_reference.py`` writes the equations
out).  It gives what ``post_ln.REQUIRED`` lists; it has no training
reference, so a train cell of it fails with the harness's plain message.

The tree is lazy, as ``hybrid_ssm``'s is and for its reason (the float32
tree is 13.7 GB at the published widths): ``to_program`` draws it under
``jit`` a layer at a time, the reference pass a layer at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import reference
from benchmark.families import hybrid_ssm_reference as lazy
from benchmark.families import sparse_window_reference as ref

#: one precision below the configuration: float8 matmul operands, the
#: router's with them
CONTROL = "fp8"
seed_key = reference.seed_key
weights_from_key = ref.weights_from_key


def shape_of(config: dict) -> dict:
    """From the zoo class's constructor arguments.  ``layers`` keeps its
    name: ``decode_scan_tick_device_ms`` divides by it (every layer
    calls the paged kernel once a tick)."""
    c = config["ctor"]
    pattern = [int(x) for x in c["layer_pattern"]]
    routed = [int(x) for x in c["routed_layers"]]
    held = tuple(c["held"]) if c.get("held") else (0, c["n_experts"])
    return {**({"init_std": config["init_std"]} if "init_std" in config else {}),
            "d": c["d_model"], "layers": len(pattern), "pattern": pattern,
            "routed": routed, "full_layers": pattern.count(0),
            "win_layers": len(pattern) - pattern.count(0),
            "routed_layers": sum(routed),
            "heads": c["n_heads"], "kv_heads": c["n_kv_heads"],
            "win_kv_heads": c["window_kv_heads"], "qk_dim": c["qk_dim"],
            "v_dim": c["v_dim"], "rotary": c["rotary_dim"],
            "theta": float(c["rope_theta"]),
            "win_theta": float(c["window_rope_theta"]),
            "value_scale": float(c.get("value_scale") or 1.0),
            "window": c["window"], "win_sink": bool(c.get("window_sink", True)),
            "full_sink": bool(c.get("full_sink", False)),
            "ff": c["d_ff"], "expert_ff": c["expert_ff"],
            "experts": c["n_experts"], "top_k": c["top_k"], "held": held,
            "eps": float(c["eps"]), "vocab": c["vocab_size"]}


def layout_of(net) -> tuple:
    """("emb",) | (group, first layer of its group, blocks) | ("head",)
    for each layer of the net: a run of the program holds consecutive
    layers of one attention kind and one feed-forward kind."""
    out, seen = [], {g: 0 for g in ref.GROUPS}
    for i, ly in enumerate(net.layers):
        if i == 0:
            out.append(("emb",))
        elif i == len(net.layers) - 1:
            out.append(("head",))
        else:
            g = ref.group_of(ly.window is not None, ly.n_experts is not None)
            out.append((g, seen[g], ly.n_blocks))
            seen[g] += ly.n_blocks
    return tuple(out)


def to_program(w, layout):
    """The program's ``params_tree``: every lazy leaf drawn (and rounded
    as its ``astype`` recorded), a run's leaves stacked on the leading
    axis the program scans."""
    tree = {}
    for i, entry in enumerate(layout):
        if len(entry) == 1:
            tree[f"layer_{i}"] = {k: leaf.whole()
                                  for k, leaf in w[entry[0]].items()}
        else:
            group, lo, n = entry
            tree[f"layer_{i}"] = lazy.stack_layers(w[group], lo, n)
    return tree


def from_program(tree, layout):
    """Back to the reference's tree, as arrays."""
    out = {"emb": tree["layer_0"], "head": tree[f"layer_{len(layout) - 1}"]}
    for g in ref.GROUPS:
        runs = [tree[f"layer_{i}"] for i, e in enumerate(layout) if e[0] == g]
        if runs:
            out[g] = {k: jnp.concatenate([r[k] for r in runs])
                      for k in runs[0]}
    return out


def leaf_norms(tree) -> dict:
    """One norm per parameter (stacked leaves layer by layer) of a
    reference-shaped tree, lazy or not."""
    out = {}
    for group, leaves in tree.items():
        for name, a in leaves.items():
            a = a.whole() if isinstance(a, lazy.Leaf) else a
            axes = tuple(range(1, a.ndim)) if group in ref.GROUPS else None
            out[f"{group}.{name}"] = jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32)), axis=axes))
    return out


def served_token_gaps(w, shape: dict, seq, t0: int, quant=None):
    return ref.served_token_gaps(w, shape, seq, t0, quant)


# ---------------------------------------------------------------------------
# costs: what the algorithm needs of THIS chip, from shapes
# ---------------------------------------------------------------------------
def pairs_per_token(shape: dict) -> float:
    """Token-expert pairs a token makes with experts held here, a routed
    layer, as expected of a router that spreads evenly."""
    return shape["top_k"] * shape["held"][1] / shape["experts"]


def matmul_params(shape: dict) -> float:
    """Parameters a token meets in this chip's matrix products (no
    table, no head): every layer's projections and router, the dense
    feed-forwards, and the held experts its pairs reach."""
    d, H, dk, dv = shape["d"], shape["heads"], shape["qk_dim"], shape["v_dim"]
    attn = lambda hkv: d * H * dk + d * hkv * (dk + dv) + H * dv * d
    dense = len(shape["routed"]) - shape["routed_layers"]
    return (shape["full_layers"] * attn(shape["kv_heads"])
            + shape["win_layers"] * attn(shape["win_kv_heads"])
            + dense * 3 * d * shape["ff"]
            + shape["routed_layers"] * (
                d * shape["experts"]
                + pairs_per_token(shape) * 3 * d * shape["expert_ff"]))


def _attended(shape, first_ctx, last_ctx):
    """(sum of the contexts, sum of min(context, window)) over the
    tokens whose context lengths run from first_ctx to last_ctx."""
    n = last_ctx - first_ctx + 1
    w = shape["window"]
    short = max(0, min(last_ctx, w) - first_ctx + 1)    # contexts <= window
    lo = first_ctx
    return ((first_ctx + last_ctx) * n / 2.0,
            (lo + min(last_ctx, w)) * short / 2.0 + (n - short) * w)


def _forward_flops(shape, first_ctx, last_ctx, heads_out) -> float:
    n = last_ctx - first_ctx + 1
    if n <= 0:
        return 0.0
    ctx, win_ctx = _attended(shape, first_ctx, last_ctx)
    per_key = 2.0 * shape["heads"] * (shape["qk_dim"] + shape["v_dim"])
    return (2.0 * matmul_params(shape) * n
            + per_key * (shape["full_layers"] * ctx
                         + shape["win_layers"] * win_ctx)
            + 2.0 * shape["d"] * shape["vocab"] * heads_out)


def serve_work(shape: dict, triples) -> dict:
    """Forward operations; the decode reads' context sums (token j of a
    request attends its prompt's t0 keys and j - 1 more in a full layer,
    ``min(., window)`` of them in a window layer); the decode ticks'
    tokens; and for the expert kernel the prompts prefilled, their
    tokens, and the reads of a held expert their routed layers needed
    (an expert none of a prompt's t0 x top_k picks chose is not read)."""
    out = dict.fromkeys(("flops", "ctx_sum", "win_ctx_sum", "decode_tokens",
                         "prefills", "prefill_tokens",
                         "prefill_expert_reads"), 0.0)
    miss = 1.0 - 1.0 / shape["experts"]
    for t0, lo, hi in triples:
        if lo == 1:                     # the prefill made token 1
            out["flops"] += _forward_flops(shape, 1, t0, 1)
            out["prefills"] += 1
            out["prefill_tokens"] += t0
            out["prefill_expert_reads"] += shape["routed_layers"] \
                * shape["held"][1] * (1.0 - miss ** (t0 * shape["top_k"]))
            lo = 2
        if hi >= lo:
            n = hi - lo + 1
            first, last = t0 + lo - 1, t0 + hi - 1
            out["flops"] += _forward_flops(shape, first, last, n)
            ctx, win_ctx = _attended(shape, first, last)
            out["ctx_sum"] += ctx
            out["win_ctx_sum"] += win_ctx
            out["decode_tokens"] += n
    return out


def train_flops_per_token(shape: dict, seq: int) -> float:
    """Forward + backward, were it trained: three times the forward."""
    return 3.0 * _forward_flops(shape, seq / 2.0, seq / 2.0, 1)


def _paged_attention(shape, facts, traffic, events, args, itemsize: int = 2):
    """Every generated token reads the K and V of its live context once
    a layer, at the widths the layer's kind has: its K/V heads' rows of
    ``qk_dim`` and of ``v_dim`` -- all of the context in a full layer,
    the window's share of it in a window layer."""
    row = shape["qk_dim"] + shape["v_dim"]
    keys = lambda hkv: (shape["full_layers"] * hkv[0] * facts["ctx_sum"]
                        + shape["win_layers"] * hkv[1] * facts["win_ctx_sum"])
    H = shape["heads"]
    return {"flops": 2.0 * row * keys((H, H)),
            "bytes": float(itemsize) * row
            * keys((shape["kv_heads"], shape["win_kv_heads"]))}


def _expert_ffn(shape, facts, traffic, events, args, itemsize: int = 2):
    """An event is one routed layer of one call.  It needs each held
    expert's three matrices ONCE (a prefill's: those its prompt's picks
    reach), and its pairs' rows in and out; 6 x d x ff operations a
    pair.  Pairs as expected of an even router (``pairs_per_token``):
    what the algorithm needs, whatever implements it -- no padding
    rows, no second read."""
    d, ff, held = shape["d"], shape["expert_ff"], shape["held"][1]
    decode_events = events - facts["prefills"] * shape["routed_layers"]
    reads = max(decode_events, 0.0) * held + facts["prefill_expert_reads"]
    pairs = ((facts["decode_tokens"] + facts["prefill_tokens"])
             * shape["routed_layers"] * pairs_per_token(shape))
    return {"flops": 6.0 * d * ff * pairs,
            "bytes": float(itemsize) * (reads * 3 * d * ff + pairs * 2 * d)}


KERNEL_COSTS = {"paged_attention": _paged_attention,
                "expert_ffn": _expert_ffn}
