"""A second family, for ``test_family_seam.py`` alone: the post-LN
block's arithmetic again (it borrows ``benchmark/reference.py`` and
``benchmark/costs.py``), but its own names for the sizes, weights at
another scale drawn in another leaf order, so that nothing of it agrees
with ``benchmark.families.post_ln`` but the program it drives.  It is
what a later PR's family file looks like to the harness: one module
that gives what ``post_ln``'s docstring lists."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import reference
from benchmark.families import post_ln

CONTROL = "fp8"
SCALE = 0.03
seed_key = reference.seed_key
leaf_norms = reference.leaf_norms
layout_of, to_program, from_program = (post_ln.layout_of, post_ln.to_program,
                                       post_ln.from_program)


def shape_of(config: dict) -> dict:
    # ``layers`` keeps its name: decode_scan_tick_device_ms divides by it
    c = config["ctor"]
    return {"width": c["d_model"], "layers": c["n_layers"], "n_head": c["n_heads"],
            "hidden": c["d_ff"], "vocab": c["vocab_size"], "positions": c["max_len"],
            "outputs": c.get("n_classes", c["vocab_size"])}


def _post_ln(shape: dict) -> dict:
    return {"d": shape["width"], "layers": shape["layers"], "heads": shape["n_head"],
            "ff": shape["hidden"], "vocab": shape["vocab"],
            "max_len": shape["positions"], "n_out": shape["outputs"]}


def weights_from_key(shape: dict, key):
    """The reference's tree, leaves drawn last to first at N(0, 0.03)."""
    like = jax.eval_shape(
        lambda k: reference.weights_from_key(_post_ln(shape), k), key)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(like)
    drawn = []
    for i, (path, leaf) in enumerate(reversed(leaves)):
        w = SCALE * jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                      jnp.float32)
        gain = path[-1].key == "g" or path[-1].key.endswith("_g")
        drawn.append(1.0 + w if gain else w)
    return jax.tree_util.tree_unflatten(treedef, drawn[::-1])


def served_token_gaps(w, shape: dict, seq, t0: int, quant=None):
    return reference.served_token_gaps(w, shape["n_head"], seq, t0, quant)


def follow_training(shape: dict, adam: dict, seed: int, batches, rows: int,
                    quant=None, batch_rows=None):
    return reference.follow_training(
        _post_ln(shape), adam, seed, batches, rows, quant, batch_rows,
        make=lambda _, seed: weights_from_key(shape, seed_key(seed)))


def serve_work(shape: dict, triples) -> dict:
    return post_ln.serve_work(_post_ln(shape), triples)


def train_flops_per_token(shape: dict, seq: int) -> float:
    return post_ln.train_flops_per_token(_post_ln(shape), seq)


KERNEL_COSTS = {
    name: (lambda cost: lambda shape, *rest: cost(_post_ln(shape), *rest))(cost)
    for name, cost in post_ln.KERNEL_COSTS.items()}
