"""The seam between the harness and an architecture: a configuration
names its ``family`` and the harness asks that module for shape,
weights, reference and costs.  A second family (``wide_init_family.py``,
one file beside this one) goes through both drivers on the CPU at the
tiny size; the harness's four files name no family and no size of one.
Nothing here is a measurement."""
import ast
import copy
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

import wide_init_family  # noqa: E402
from benchmark import drivers, readers, run  # noqa: E402
from benchmark.families import post_ln  # noqa: E402

GELU = {"TransformerEncoderBlock": {"activation": "gelu"}}
TINY = dict(n_layers=2, d_model=32, n_heads=4, d_ff=64, vocab_size=64,
            max_len=64, seq_len=16)
SEED = 2 ** 31 + 23
CONFIG = {
    "train": {"family": "wide_init_family",
              "zoo_class": "deeplearning4j_tpu.zoo.bert.Bert",
              "ctor": dict(TINY, n_classes=2, compute_dtype="bfloat16"),
              "layer_overrides": GELU,
              "updater": {"type": "Adam", "learning_rate": {
                  "type": "ramp", "initial": 2e-5, "warmup": 100}},
              "adam": {"lr": 2e-5, "warmup": 100, "beta1": 0.9, "beta2": 0.999,
                       "eps": 1e-8}},
    "serve_closed": {"family": "wide_init_family",
                     "zoo_class": "deeplearning4j_tpu.zoo.gpt.Gpt",
                     "ctor": TINY, "layer_overrides": GELU}}
# limits: the real train cell's, the rehearsal's token_gap (the sound
# runs' readings are in the test below)
CELL = {
    "train": {"driver": "train", "reference_rows": 2,
              "traffic": {"batch": 4, "seq": 16, "classes": 2, "ring": 8,
                          "labels": "one_class"},
              "limits": {"grad_norm_gap": 0.05, "change_norm_gap": 0.02,
                         "change_share_gap_median": 0.00055}},
    "serve_closed": {
        "driver": "serve_closed",
        "server": {"compute_dtype": "bfloat16", "n_slots": 4, "max_len": 64,
                   "block_size": 8, "tick_batch": 8, "prefix_cache": True},
        "traffic": {"loop": "closed", "clients": 4,
                    "prompt_len": {"dist": "uniform", "lo": 5, "hi": 20},
                    "n_new": {"dist": "uniform", "lo": 16, "hi": 40},
                    "sizes_seed": 0, "n_sizes": 64, "ramp_seconds": 0.3,
                    "trace_seconds": 0.2, "compare_requests": 3},
        "limits": {"token_gap": 0.05}}}
HARNESS = ("run.py", "drivers.py", "readers.py", "study.py")


def _weights_of_the_first_family(monkeypatch, config):
    """The program is handed post_ln's weights; the reference is the
    cell's own family's."""
    whole = drivers.seed_weights
    monkeypatch.setattr(
        drivers, "seed_weights", lambda net, family, shape, seed, dtype="float32":
        whole(net, post_ln, post_ln.shape_of(config), seed, dtype))


def _reference_of_the_first_family(monkeypatch, config):
    """The program runs the cell's own family's weights; what it made
    is compared with post_ln's reference."""
    whole = run.compare
    monkeypatch.setattr(
        run, "compare", lambda driver, family, config, cell, seed, shape, produced:
        whole(driver, post_ln, config, cell, seed, post_ln.shape_of(config),
              produced))


@pytest.mark.parametrize("fault", [None, _weights_of_the_first_family,
                                   _reference_of_the_first_family],
                         ids=["sound", "first_family_weights",
                              "first_family_reference"])
@pytest.mark.parametrize("driver", ["train", "serve_closed"])
def test_a_second_family_runs_through_both_drivers(monkeypatch, driver, fault):
    """Sound, on this seed and four others: grad 4.6e-3 (at most
    1.1e-2), change 3.3e-3, share 1.2e-4 (at most 2.4e-4), token_gap at
    most 1.5e-3.  With either side taken from the first family: grad
    0.65-2.3, change 0.38 and more, share 0.04-0.17, token_gap 0.5-0.9."""
    import jax
    config, cell = CONFIG[driver], CELL[driver]
    if fault is not None:
        fault(monkeypatch, config)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    result = run.run_cell("a-cell-of-the-second-family", manifest, cell, config,
                          SEED, 0.5, 0, jax.devices()[:1], {})
    assert result["correct"] is (fault is None), result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["compared"]) == set(cell["limits"])


@pytest.mark.parametrize("family", [post_ln, wide_init_family],
                         ids=["post_ln", "wide_init_family"])
def test_a_family_gives_what_the_harness_asks_for(family):
    """``post_ln.REQUIRED`` is the list the docstring beside it sets
    out; the harness reads ``vocab`` from the shape and nothing else."""
    missing = [a for a in post_ln.REQUIRED if not hasattr(family, a)]
    assert not missing, missing
    assert set(family.KERNEL_COSTS) >= {"paged_attention", "flash_fwd", "flash_bwd"}
    assert hasattr(family, "follow_training")         # both train too
    shape = family.shape_of(CONFIG["train"])
    assert shape["vocab"] == 64 and shape["layers"] == 2
    assert drivers.family_of(dict(CONFIG["train"], family=family.__name__)) is family
    with pytest.raises(KeyError, match="family"):
        drivers.family_of({"ctor": TINY})


def test_a_train_cell_of_a_family_without_a_training_reference_fails_plainly(
        monkeypatch):
    monkeypatch.delattr(wide_init_family, "follow_training")
    with pytest.raises(SystemExit, match="no training reference"):
        run.run_cell("a-train-cell", {"per_layer": []}, CELL["train"],
                     CONFIG["train"], SEED, 0.5, 0, [], {})


@pytest.mark.parametrize("cost, hand", [
    ("paged_attention", 100.0 * 64.0 / 2.0), ("no_such_kernel", None)])
def test_a_kernel_cost_is_looked_up_in_the_cell_s_family(cost, hand):
    """A metric file's ``"cost"`` names a function of the cell's
    family (here the second one, whose shape post_ln's arithmetic could
    not read); a name the family does not have fails loudly."""
    shape = {"width": 8, "layers": 2, "n_head": 2, "hidden": 16, "vocab": 50,
             "positions": 32, "outputs": 50}
    trace = {"devices": {"/device:TPU:0": {"XLA Ops": [
        ("%paged_attention.7 = (bf16[8]) custom-call(", 0.0, 2.0)]}}, "host_spans": []}
    ctx = {"facts": {"window_s": 10.0, "ctx_sum": 10.0}, "trace": trace,
           "peak": {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0},
           "family": wide_init_family, "shape": shape, "traffic": {}}
    spec = {"reader": {"kind": "roofline_of", "args": {
        "pattern": "^%paged_attention[.\\d]* = ", "cost": cost}}}
    if hand is None:
        with pytest.raises(ValueError, match="no_such_kernel.*wide_init_family"):
            readers.read(spec, ctx)
    else:
        # 2 * 2 bytes * 2 layers * 8 wide * 10 tokens of context = 64 s
        assert readers.read(spec, ctx) == pytest.approx(hand)
        with pytest.raises(KeyError):       # post_ln cannot read this shape
            readers.read(spec, dict(ctx, family=post_ln))


@pytest.mark.parametrize("name", HARNESS)
def test_the_harness_names_no_family_and_no_size_of_one(name):
    """``run.py``, ``drivers.py``, ``readers.py`` and ``study.py``
    import no family module, none of the first family's two files but
    for ``costs.roofline_seconds``, and spell none of its words."""
    with open(os.path.join(ROOT, "benchmark", name)) as f:
        source = f.read()
    for word in ("Wqkv", "n_heads", "d_ff", "ctx_sum"):
        assert word not in source, (name, word)
    tree = ast.parse(source)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            modules = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            modules = [f"{node.module}.{a.name}" for a in node.names]
        else:
            modules = []
        for m in modules:
            assert "families" not in m and not m.endswith("reference"), (name, m)
        if (isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name)
                and node.value.id in ("costs", "reference")):
            assert (node.value.id, node.attr) == ("costs", "roofline_seconds"), (
                name, node.attr)


def test_master_weights_in_bfloat16_leave_no_float32_leaf():
    """A served-only configuration that states bfloat16 master weights
    puts 2 bytes a parameter on the device through the harness, and its
    reference is given the same rounded values."""
    import jax
    import jax.numpy as jnp
    config = copy.deepcopy(CONFIG["serve_closed"])
    config.update(family="benchmark.families.post_ln",
                  precision={"master_weights": "bfloat16"})
    family, dtype = drivers.family_of(config), drivers.master_dtype(config)
    assert (family, dtype) == (post_ln, "bfloat16")
    assert drivers.master_dtype(CONFIG["serve_closed"]) == "float32"
    shape = family.shape_of(config)
    net = drivers.build_net(config)
    drivers.seed_weights(net, family, shape, SEED, dtype)
    leaves = jax.tree_util.tree_leaves(net.params_tree)
    assert leaves and {str(a.dtype) for a in leaves} == {"bfloat16"}
    ref = family.to_program(
        drivers.seed_tree(family, shape, family.seed_key(SEED), dtype),
        family.layout_of(net))
    exact = family.to_program(family.weights_from_key(shape, family.seed_key(SEED)),
                              family.layout_of(net))
    for a, b, c in zip(leaves, jax.tree_util.tree_leaves(ref),
                       jax.tree_util.tree_leaves(exact)):
        assert b.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)), np.asarray(b))
    assert any(not np.array_equal(np.asarray(b), np.asarray(c)) for b, c in zip(
        jax.tree_util.tree_leaves(ref), jax.tree_util.tree_leaves(exact)))
