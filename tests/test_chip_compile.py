"""The main path's Pallas kernels, held to the CHIP's compiler at the
widths ``chip_smoke.py`` runs — no chip needed: the TPU compiler is
installed here and compiles for a v5e that is described, not attached.

Interpret mode (every other kernel test) cannot see what Mosaic
refuses: this file is where ``_paged_decode_pallas``'s batched mat-vec
("failed to parse 'lhs_non_contracting_dims'") and the un-partitionable
flash call under a multi-device mesh ("wrap the call in a shard_map")
would have been caught.  A compile that passes is not a run.

The topology is described inside a module-scoped fixture — never at
import, in a ``skipif`` or in ``parametrize`` arguments: only one
process at a time may load the TPU's library, and every xdist worker
imports every test file.  Keep these tests in this one file.
"""
import glob
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from deeplearning4j_tpu import kernels

flash_mod = importlib.import_module(
    "deeplearning4j_tpu.kernels.flash_attention")
paged_mod = importlib.import_module(
    "deeplearning4j_tpu.kernels.paged_attention")

# (n_heads, d_head) at d_model 768: the zoo.Gpt default and GPT-2's
HEADS = [(6, 128), (12, 64)]


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 host, with the persistent compile cache off
    around the module (such a compile is written to it but cannot be
    read back without a chip — the next run would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    had_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    if had_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_paths(monkeypatch):
    """Steer the kernels onto their compiled path: during such a
    compile ``jax.default_backend()`` still says cpu."""
    monkeypatch.setattr(flash_mod, "_interpret", lambda: False)
    monkeypatch.setattr(paged_mod, "_interpret", lambda: False)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("h,dh", HEADS, ids=["6x128", "12x64"])
@pytest.mark.parametrize("entry", ["decode", "verify"])
def test_paged_kernels_compile_for_v5e(one_chip, chip_paths, entry, h,
                                       dh, dtype):
    """GenerationServer(n_slots=8, max_len=2048) geometry: block 16,
    128-entry tables, the default 1024-block pool (+ scratch block 0);
    float32 is the server's default pool dtype, bf16 the other."""
    B, bs, mb, nb, W = 8, 16, 128, 1025, 5

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = S((nb, h, bs, dh), dtype)
    table, pos = S((B, mb), jnp.int32), S((B,), jnp.int32)
    if entry == "decode":
        fn, q = paged_mod._paged_decode_pallas, S((B, h, dh), dtype)
    else:
        fn, q = paged_mod._paged_verify_pallas, S((B, W, h, dh), dtype)
    text = _compiled_text(
        lambda q, k, v, t, p: fn(q, k, v, t, p, dh ** -0.5),
        q, pool, pool, table, pos)
    assert "tpu_custom_call" in text


def _attention_fn(causal, backward):
    """attention() as TransformerEncoderBlock calls it ([b, t, h, d]
    operands), forward or forward + all three cotangents."""
    def fwd(q, k, v, bias):
        return kernels.attention(q, k, v, bias=bias, causal=causal,
                                 layout="bthd")

    if not backward:
        return fwd

    def loss(q, k, v, bias):
        return jnp.sum(fwd(q, k, v, bias).astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("b,t,causal", [(8, 2048, True), (32, 512, False)],
                         ids=["causal_t2048", "bias_t512"])
@pytest.mark.parametrize("h,dh", HEADS, ids=["6x128", "12x64"])
def test_flash_kernels_compile_for_v5e(one_chip, chip_paths, h, dh, b, t,
                                       causal, backward):
    """The GPT train shape (b=8, t=2048, causal) and the BERT one
    (b=32, t=512, [b, tk] padding bias), bf16, routed by attention()
    itself (d=64 takes the transposed layout there)."""
    qkv = jax.ShapeDtypeStruct((b, t, h, dh), jnp.bfloat16,
                               sharding=one_chip)
    bias = (None if causal else
            jax.ShapeDtypeStruct((b, t), jnp.float32, sharding=one_chip))
    kernels.reset_route_log()
    text = _compiled_text(_attention_fn(causal, backward),
                          qkv, qkv, qkv, bias)
    assert kernels.route_log() == (("flash", t, dh),)
    # forward, or forward + dK/dV + dQ
    assert text.count("tpu_custom_call") >= (3 if backward else 1)


def test_flash_compiles_mapped_over_a_2x2_mesh(topo, chip_paths):
    """ShardedTrainer(MeshConfig(data=2, model=2)) on zoo.Gpt(): GSPMD
    cannot partition a Mosaic kernel, so under the declared mesh the
    flash call maps itself over batch x heads (fwd + bwd)."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    qkv = jax.ShapeDtypeStruct(
        (8, 2048, 6, 128), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data", None, "model", None)))
    kernels.reset_route_log()
    with mesh, kernels.trace_mesh(mesh):
        text = _compiled_text(_attention_fn(True, True),
                              qkv, qkv, qkv, None)
    assert kernels.route_log() == (("flash", 2048, 128),)
    assert text.count("tpu_custom_call") >= 3


# ---------------------------------------------------------------------------
# The names the benchmark reads (ISSUE 25).  Every file of
# benchmark/layer_metrics/ that reads a kernel or a program BY NAME is
# held to the compiled programs here: a rename fails tier-1 instead of
# silencing a metric on the chip.
# ---------------------------------------------------------------------------
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _metric_args(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)["reader"]["args"]


def _trace_names(compiled):
    """What the profiler shows of a compiled program: its module on
    the ``XLA Modules`` line as ``<name>(<program id>)``, each
    instruction on ``XLA Ops`` as its HLO line."""
    text = compiled.as_text()
    module = re.match(r"HloModule ([^\s,]+)", text).group(1) + "(1)"
    ops = [re.sub(r"^ROOT ", "", ln.strip()) for ln in text.splitlines()]
    return module, ops


def _matches(pattern, names):
    rx = re.compile(pattern)
    return [n for n in names if rx.search(n)]


def _on_chip(tree, one_chip):
    """The shapes of ``tree``'s arrays, placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=one_chip), tree)


def test_flash_names_at_bert_large_widths(one_chip, chip_paths):
    """Flash forward + backward at the benchmark's (16, 512, 16, 64),
    bf16, no bias: the forward kernel is ``%flash_fwd``, the backward's
    two ``%flash_bwd_dkv`` and ``%flash_bwd_dq``, whatever ``jvp`` and
    ``transpose`` wrap them in."""
    qkv = jax.ShapeDtypeStruct((16, 512, 16, 64), jnp.bfloat16,
                               sharding=one_chip)
    kernels.reset_route_log()
    compiled = jax.jit(_attention_fn(False, True)).lower(
        qkv, qkv, qkv, None).compile()
    assert kernels.route_log() == (("flash", 512, 64),)
    _, ops = _trace_names(compiled)
    assert len(_matches(_metric_args("flash_forward_roofline")["pattern"],
                        ops)) == 1
    bwd = _metric_args("flash_backward_roofline")
    assert len(_matches(bwd["pattern"], ops)) == bwd["events_per_call"]


def test_paged_kernel_name_at_the_benchmark_geometry(one_chip, chip_paths):
    """The decode read of ``bert-large-causal.closed-decode``: 64
    slots, 32-entry tables, 2,049 blocks of 16 x (16 heads x 64), bf16."""
    B, h, dh, bs, mb, nb = 64, 16, 64, 16, 32, 2049

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = S((nb, h, bs, dh), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, t, p: paged_mod._paged_decode_pallas(
            q, k, v, t, p, dh ** -0.5)).lower(
        S((B, h, dh), jnp.bfloat16), pool, pool, S((B, mb), jnp.int32),
        S((B,), jnp.int32)).compile()
    _, ops = _trace_names(compiled)
    for name in ("paged_attention_roofline", "decode_scan_tick_device_ms"):
        args = _metric_args(name)
        pattern = args.get("per_events_of", args)["pattern"]
        assert len(_matches(pattern, ops)) == 1, name


@pytest.fixture(scope="module")
def named_programs(one_chip):
    """{program: (module name as the trace shows it, instruction
    lines)} of a 2-layer train step, a tiny decode scan and both admit
    programs, each lowered from the package's own jitted callable for
    the described chip (nothing runs; the server only stages tiny
    weights and its pool on the CPU)."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.models.multi_layer_network import \
        MultiLayerNetwork
    from deeplearning4j_tpu.parallel import GenerationServer
    from deeplearning4j_tpu.zoo.bert import Bert
    from deeplearning4j_tpu.zoo.gpt import Gpt
    mp = pytest.MonkeyPatch()
    mp.setattr(flash_mod, "_interpret", lambda: False)
    mp.setattr(paged_mod, "_interpret", lambda: False)
    mp.setattr(paged_mod, "_route", lambda: "pallas")
    out = {}

    def S(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    try:
        net = MultiLayerNetwork(Bert(
            n_layers=2, d_model=256, n_heads=4, d_ff=512, vocab_size=128,
            max_len=512, seq_len=512, n_classes=2,
            compute_dtype="bfloat16", use_flash=True).conf()).init()
        net._build_solver()
        batch = net._batch_dict(DataSet(
            np.zeros((2, 512), np.int32), np.zeros((2, 2), np.float32)))
        args = _on_chip((net.params_tree, net.opt_state, net.state_tree,
                         jnp.zeros((), jnp.int32), batch,
                         net._rng.next_key()), one_chip)
        out["train_step"] = _trace_names(
            net._solver._step.lower(*args, 1.0).compile())

        gpt = MultiLayerNetwork(Gpt(
            n_layers=2, d_model=256, n_heads=2, d_ff=512, vocab_size=128,
            max_len=64, seq_len=64).conf()).init()
        srv = GenerationServer(gpt, n_slots=8, max_len=64, block_size=16,
                               tick_batch=2, compute_dtype="bfloat16")
        try:
            pool = _on_chip((*srv._params, srv._kc, srv._vc, srv._state),
                            one_chip)
            out["decode_scan"] = _trace_names(
                srv._decode_scan(2, False).lower(*pool).compile())
            # operands as GenerationServer._admit hands them over: a
            # 33-token prompt padded to the 64 bucket, then the same
            # prompt again with its two full blocks cached
            key = _on_chip(jax.random.PRNGKey(0), one_chip)
            slot = (S(()), S(()), S(()), key, S((), jnp.float32), S(()),
                    S((), jnp.float32))     # slot n_new eos key temp tk tp
            rows = (S((srv.max_blocks,)), S((srv.max_blocks,)))
            out["admit_miss"] = _trace_names(srv._admit_miss_fn(64).lower(
                *pool, S((1, 64)), S(()), *slot, S((4,)), *rows).compile())
            out["admit_hit"] = _trace_names(srv._admit_hit_fn(16, 2).lower(
                *pool, S((1, 16)), S(()), S(()), S(()), *slot, S((2,)),
                S((1,)), *rows).compile())
        finally:
            srv.shutdown(drain=False, timeout=30.0)
    finally:
        mp.undo()
    return out


def test_programs_and_their_kernels_carry_the_package_s_names(
        named_programs):
    """Modules ``jit_train_step``, ``jit_decode_scan``,
    ``jit_admit_miss`` and ``jit_admit_hit``; inside the train step the
    flash kernels keep their names under ``jvp`` and ``transpose``
    (one forward and two backward kernels a layer), inside the layer
    scan of a decode tick the paged kernel keeps its own."""
    assert {k: v[0] for k, v in named_programs.items()} == {
        "train_step": "jit_train_step(1)",
        "decode_scan": "jit_decode_scan(1)",
        "admit_miss": "jit_admit_miss(1)",
        "admit_hit": "jit_admit_hit(1)"}
    _, train = named_programs["train_step"]
    _, scan = named_programs["decode_scan"]
    for kernel, lines, n in (("flash_fwd", train, 2),
                             ("flash_bwd_dkv", train, 2),
                             ("flash_bwd_dq", train, 2),
                             ("paged_attention", scan, 1)):
        assert len(_matches(rf"^%{kernel}[.\d]* = .*custom-call\(",
                            lines)) == n, kernel
    # the scopes an operator reads in xprof are in the op names
    text = "\n".join(train)
    for scope in ("forward", "backward", "optimizer"):
        assert f"jit(train_step)/{scope}/" in text, scope
    text = "\n".join(scan)
    assert "/decode_tick/" in text and "/sample/" in text


def _name_keyed_metrics():
    """Every file of benchmark/layer_metrics/ that finds its events by
    a pattern, but for the four PR 24 wrote against JAX's positional
    names (a benchmark issue retires those)."""
    positional = {"flash_fwd_roofline", "flash_bwd_roofline",
                  "paged_attn_roofline", "decode_tick_device_ms"}
    names = []
    for path in sorted(glob.glob(os.path.join(
            ROOT, "benchmark", "layer_metrics", "*.json"))):
        name = os.path.basename(path)[:-len(".json")]
        if name not in positional and "pattern" in _metric_args(name):
            names.append(name)
    return names


@pytest.mark.parametrize("name", _name_keyed_metrics())
def test_name_keyed_metric_matches_a_compiled_name(named_programs, name):
    """Each ``pattern`` (and ``per_events_of.pattern``) matches a
    module or an instruction of the programs compiled above: a rename
    in the package fails here, not silently on the chip."""
    modules = [m for m, _ in named_programs.values()]
    ops = [ln for _, lines in named_programs.values() for ln in lines]
    args = _metric_args(name)
    line = modules if args.get("line") == "XLA Modules" else ops
    assert _matches(args["pattern"], line), args["pattern"]
    if "per_events_of" in args:
        assert _matches(args["per_events_of"]["pattern"], ops)
