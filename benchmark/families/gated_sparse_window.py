"""The gated sparse-expert decoder family: ``sparse_window``'s stack of
pre-norm grouped-query attention blocks, full or sliding-window by a
per-layer pattern, with what Laguna-XS.2 adds to it -- a number of query
heads and of rotary lanes a KIND of layer, a YaRN-scaled rotary base, a
sigmoid gate a query head on the attention's output, a SHARED expert
beside the routed ones and a factor on the routed sum -- and every
expert held on the chip (``gated_sparse_window_reference.py`` writes the
equations out).  It gives what ``post_ln.REQUIRED`` lists; it has no
training reference, so a train cell of it fails with the harness's plain
message.

The tree is lazy, as ``sparse_window``'s is, and a routed run's expert
matrices are lazy an EXPERT at a time: the reference pass never holds a
routed layer whole (3.4 GB in float32).  The program's layout is
``sparse_window``'s (runs of consecutive layers of one kind), so the way
in and out of it is that module's.
"""
from __future__ import annotations

from benchmark import reference
from benchmark.families import gated_sparse_window_reference as ref
from benchmark.families.sparse_window import (_attended, from_program,
                                              layout_of, leaf_norms,
                                              to_program)

#: one precision below the configuration: float8 matmul operands, the
#: router's and the gate's with them
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
    if c["qk_dim"] != c["v_dim"] or c.get("value_scale") \
            or c.get("window_sink") or c.get("full_sink"):
        raise ValueError("the gated family has keys as wide as values, no "
                         "value scale and no sink (sparse_window has them)")
    return {**({"init_std": config["init_std"]} if "init_std" in config else {}),
            "d": c["d_model"], "layers": len(pattern), "pattern": pattern,
            "routed": routed, "full_layers": pattern.count(0),
            "win_layers": len(pattern) - pattern.count(0),
            "routed_layers": sum(routed),
            "heads": c["n_heads"],
            "win_heads": c.get("window_heads") or c["n_heads"],
            "kv_heads": c["n_kv_heads"],
            "win_kv_heads": c["window_kv_heads"], "qk_dim": c["qk_dim"],
            "v_dim": c["v_dim"], "rotary": c["rotary_dim"],
            "win_rotary": c.get("window_rotary_dim") or c["rotary_dim"],
            "theta": float(c["rope_theta"]),
            "win_theta": float(c["window_rope_theta"]),
            "rope_scaling": c.get("rope_scaling"),
            "win_rope_scaling": c.get("window_rope_scaling"),
            "window": c["window"], "gate": bool(c.get("gate")),
            "ff": c["d_ff"], "expert_ff": c["expert_ff"],
            "shared_ff": c.get("shared_ff") or 0,
            "routed_scale": float(c.get("routed_scale") or 1.0),
            "experts": c["n_experts"], "top_k": c["top_k"], "held": held,
            "eps": float(c["eps"]), "vocab": c["vocab_size"]}


def served_token_gaps(w, shape: dict, seq, t0: int, quant=None):
    return ref.served_token_gaps(w, shape, seq, t0, quant)


# ---------------------------------------------------------------------------
# costs: what the algorithm needs of THIS chip, from shapes
# ---------------------------------------------------------------------------
def reached(shape: dict, rows: float) -> float:
    """Held experts that ``rows`` tokens' picks reach in one routed
    layer, as expected of a router that spreads evenly: each of a row's
    ``top_k`` distinct picks misses a given expert with probability
    ``1 - top_k / experts``."""
    return shape["held"][1] * (
        1.0 - (1.0 - shape["top_k"] / shape["experts"]) ** rows)


def pairs_per_token(shape: dict) -> float:
    """Token-expert pairs a token makes with experts held here, a routed
    layer."""
    return shape["top_k"] * shape["held"][1] / shape["experts"]


def matmul_params(shape: dict) -> float:
    """Parameters a token meets in this chip's matrix products (no
    table, no head): every layer's projections, gate and router, the
    dense feed-forwards, the shared experts, and the held experts its
    pairs reach."""
    d, dh = shape["d"], shape["qk_dim"]
    gate = 1 if shape["gate"] else 0
    attn = lambda H, hkv: d * H * (2 * dh + gate) + 2 * d * hkv * dh
    dense = len(shape["routed"]) - shape["routed_layers"]
    return (shape["full_layers"] * attn(shape["heads"], shape["kv_heads"])
            + shape["win_layers"] * attn(shape["win_heads"],
                                         shape["win_kv_heads"])
            + dense * 3 * d * shape["ff"]
            + shape["routed_layers"] * (
                d * shape["experts"] + 3 * d * shape["shared_ff"]
                + pairs_per_token(shape) * 3 * d * shape["expert_ff"]))


def _forward_flops(shape, first_ctx, last_ctx, heads_out) -> float:
    n = last_ctx - first_ctx + 1
    if n <= 0:
        return 0.0
    ctx, win_ctx = _attended(shape, first_ctx, last_ctx)
    per_key = 2.0 * (shape["qk_dim"] + shape["v_dim"])
    return (2.0 * matmul_params(shape) * n
            + per_key * (shape["full_layers"] * shape["heads"] * ctx
                         + shape["win_layers"] * shape["win_heads"] * win_ctx)
            + 2.0 * shape["d"] * shape["vocab"] * heads_out)


def serve_work(shape: dict, triples) -> dict:
    """Forward operations; the decode reads' context sums (token j of a
    request attends its prompt's t0 keys and j - 1 more in a full layer,
    ``min(., window)`` of them in a sliding layer); the decode ticks'
    tokens; and for the expert kernel the prompts prefilled, their
    tokens, and the reads of a held expert their routed layers needed
    (an expert none of a prompt's picks chose is not read)."""
    out = dict.fromkeys(("flops", "ctx_sum", "win_ctx_sum", "decode_tokens",
                         "prefills", "prefill_tokens",
                         "prefill_expert_reads"), 0.0)
    for t0, lo, hi in triples:
        if lo == 1:                     # the prefill made token 1
            out["flops"] += _forward_flops(shape, 1, t0, 1)
            out["prefills"] += 1
            out["prefill_tokens"] += t0
            out["prefill_expert_reads"] += shape["routed_layers"] \
                * reached(shape, t0)
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
    a layer at ``dh``: all of the context in a full layer, the window's
    share of it in a sliding layer; a layer's query heads are its
    kind's."""
    row = shape["qk_dim"] + shape["v_dim"]
    keys = lambda full, win: (
        shape["full_layers"] * full * facts["ctx_sum"]
        + shape["win_layers"] * win * facts["win_ctx_sum"])
    return {"flops": 2.0 * row * keys(shape["heads"], shape["win_heads"]),
            "bytes": float(itemsize) * row
            * keys(shape["kv_heads"], shape["win_kv_heads"])}


def _expert_ffn(shape, facts, traffic, events, args, itemsize: int = 2):
    """An event is one routed layer of one call.  It needs the three
    matrices of each held expert its rows REACH, once: a decode event's
    rows are the tokens a tick decodes, a prefill's its prompt's (an
    expert no pick chose is never fetched, and with 256 held and a
    handful of rows an expert that is most of the difference); and its
    pairs' rows in and out; 6 x d x ff operations a pair.  Reached
    experts and pairs as expected of an even router: what the algorithm
    needs, whatever implements it -- no padding rows, no second read."""
    d, ff = shape["d"], shape["expert_ff"]
    decode_events = max(events - facts["prefills"] * shape["routed_layers"],
                        0.0)
    ticks = decode_events / shape["routed_layers"]
    rows_a_tick = facts["decode_tokens"] / ticks if ticks else 0.0
    reads = decode_events * reached(shape, rows_a_tick) \
        + facts["prefill_expert_reads"]
    pairs = ((facts["decode_tokens"] + facts["prefill_tokens"])
             * shape["routed_layers"] * pairs_per_token(shape))
    return {"flops": 6.0 * d * ff * pairs,
            "bytes": float(itemsize) * (reads * 3 * d * ff + pairs * 2 * d)}


KERNEL_COSTS = {"paged_attention": _paged_attention,
                "expert_ffn": _expert_ffn}
