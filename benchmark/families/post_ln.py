"""The post-LN transformer family: the encoder classifier and the
causal decoder that ``benchmark/reference.py`` writes out and
``benchmark/costs.py`` counts.  This module only binds the two.

**What a family module gives** (``REQUIRED``, and ``follow_training``
where it has a training reference).  The harness reads ``vocab`` from a
family's shape (traffic draws token ids) and hands the WHOLE shape back;
every other key is the family's own.

- ``shape_of(config)``: the sizes its reference and costs need.  A key
  a metric's ``per_events_of.each`` names (``layers``) lives here too.
- ``seed_key(seed)``, ``weights_from_key(shape, key)`` (jit-safe): the
  reference's float32 tree from the seed.
- ``layout_of(net)``, ``to_program(w, layout)``, ``from_program(tree,
  layout)`` (jit-safe): the maps between the reference's tree and the
  program's ``params_tree``; ``leaf_norms(tree)``: one norm per
  parameter of a reference-shaped tree.
- ``served_token_gaps(w, shape, seq, t0, quant=None)``: how far each
  served token's logit lies below the reference's best; ``CONTROL`` is
  the ``quant`` of the control, one precision below the configuration.
- ``follow_training(shape, adam, seed, batches, rows, quant=None,
  batch_rows=None)``: the first Adam steps.  A family without a
  training reference leaves it out, and a ``train`` cell that names it
  fails with a plain message.
- ``serve_work(shape, triples)``: ``{"flops": forward operations, ...
  any further fact its kernels' costs need}`` of the served tokens,
  from ``(prompt length, first new token, last new token)`` triples
  (tokens counted from 1; token 1 is the prefill's);
  ``train_flops_per_token(shape, seq)``.
- ``KERNEL_COSTS``: ``{name: f(shape, facts, traffic, events, args) ->
  {"flops", "bytes"}}``; a metric file's ``"cost": name`` is looked up
  here, ``events`` being the kernel's events in the traced sub-window
  and ``args`` the metric file's own.
"""
from __future__ import annotations

from benchmark import costs, reference

REQUIRED = ("shape_of", "seed_key", "weights_from_key", "layout_of",
            "to_program", "from_program", "leaf_norms", "served_token_gaps",
            "CONTROL", "serve_work", "train_flops_per_token", "KERNEL_COSTS")
CONTROL = "fp8"

seed_key = reference.seed_key
weights_from_key = reference.weights_from_key
leaf_norms = reference.leaf_norms
follow_training = reference.follow_training
train_flops_per_token = costs.train_flops_per_token


def shape_of(config: dict) -> dict:
    """From the zoo class's constructor arguments."""
    c = config["ctor"]
    return {"d": c["d_model"], "layers": c["n_layers"], "heads": c["n_heads"],
            "ff": c["d_ff"], "vocab": c["vocab_size"], "max_len": c["max_len"],
            "n_out": c.get("n_classes", c["vocab_size"])}


def layout_of(net) -> tuple:
    """'emb' | 'block' | 'none' | 'head' for each layer of the net."""
    kinds = []
    for i, ly in enumerate(net.layers):
        if i == 0:
            kinds.append("emb")
        elif i == len(net.layers) - 1:
            kinds.append("head")
        else:
            kinds.append("block" if ly.has_params() else "none")
    return tuple(kinds)


def to_program(w, kinds):
    tree, b = {}, 0
    for i, kind in enumerate(kinds):
        if kind == "block":
            tree[f"layer_{i}"] = {k: v[b] for k, v in w["blocks"].items()}
            b += 1
        else:
            tree[f"layer_{i}"] = dict(w[kind]) if kind != "none" else {}
    return tree


def from_program(tree, kinds):
    import jax.numpy as jnp
    blocks = [tree[f"layer_{i}"] for i, k in enumerate(kinds) if k == "block"]
    return {"emb": tree["layer_0"],
            "blocks": {k: jnp.stack([b[k] for b in blocks]) for k in blocks[0]},
            "head": tree[f"layer_{len(kinds) - 1}"]}


def served_token_gaps(w, shape: dict, seq, t0: int, quant=None):
    return reference.served_token_gaps(w, shape["heads"], seq, t0, quant)


def serve_work(shape: dict, triples) -> dict:
    """Forward operations, and the decode kernel's context sum: token j
    of a request attends its prompt's t0 keys and j - 1 more."""
    flops = ctx_sum = 0.0
    for t0, lo, hi in triples:
        if lo == 1:                     # the prefill made token 1
            flops += costs.lm_forward_flops(shape, 1, t0, 1)
            lo = 2
        if hi >= lo:
            flops += costs.lm_forward_flops(shape, t0 + lo - 1, t0 + hi - 1,
                                            hi - lo + 1)
            ctx_sum += (2 * t0 + lo + hi - 2) * (hi - lo + 1) / 2.0
    return {"flops": flops, "ctx_sum": ctx_sum}


def _paged_attention(shape, facts, traffic, events, args):
    return costs.paged_attention_cost(shape, facts["ctx_sum"])


def _flash(backward: bool):
    def cost(shape, facts, traffic, events, args):
        # one kernel call covers every row and head of a layer's batch
        calls = events / args.get("events_per_call", 1)
        c = costs.flash_cost(shape, traffic["batch"], traffic["seq"],
                             args.get("causal", False), backward)
        return {k: v * calls for k, v in c.items()}
    return cost


KERNEL_COSTS = {"paged_attention": _paged_attention,
                "flash_fwd": _flash(False), "flash_bwd": _flash(True)}
