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
from deeplearning4j_tpu.parallel.generation_server import _ADMIT_HEAD

flash_mod = importlib.import_module(
    "deeplearning4j_tpu.kernels.flash_attention")
paged_mod = importlib.import_module(
    "deeplearning4j_tpu.kernels.paged_attention")
ssm_mod = importlib.import_module("deeplearning4j_tpu.kernels.ssm_step")
expert_mod = importlib.import_module("deeplearning4j_tpu.kernels.expert_ffn")

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
    monkeypatch.setattr(ssm_mod, "_interpret", lambda: False)
    monkeypatch.setattr(expert_mod, "_interpret", lambda: False)


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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("entry", ["decode", "verify"])
def test_paged_read_kernels_compile_over_two_heads_a_row(one_chip, chip_paths,
                                                         entry, dtype):
    """The same server geometry at GPT-2's 12 heads of 64 as a
    kernel-route pool holds them since ISSUE 34, two a 128-lane row (6
    pool heads): the scatter-then-read kernels take 2 query rows a
    position a pool head, whose positions the mask divides out."""
    B, bs, mb, nb, W, h, dh, p = 8, 16, 128, 1025, 5, 12, 64, 2

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = S((nb, h // p, bs, p * dh), dtype)
    q = S((B, h, dh) if entry == "decode" else (B, W, h, dh), dtype)
    fn = (paged_mod._paged_decode_pallas if entry == "decode"
          else paged_mod._paged_verify_pallas)
    text = _compiled_text(
        lambda q, k, v, t, pos: fn(q, k, v, t, pos, dh ** -0.5, p),
        q, pool, pool, S((B, mb), jnp.int32), S((B,), jnp.int32))
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


def _name_chars():
    """How much of an event's name the benchmark's readers see."""
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import trace_reduce
    return trace_reduce.NAME_CHARS


def _trace_names(compiled):
    """What the profiler shows of a compiled program: its module on
    the ``XLA Modules`` line as ``<name>(<program id>)``, each
    instruction on ``XLA Ops`` as its HLO line."""
    text = compiled.as_text()
    module = re.match(r"HloModule ([^\s,]+)", text).group(1) + "(1)"
    ops = [re.sub(r"^ROOT ", "", ln.strip()) for ln in text.splitlines()]
    return module, ops


def _matches(pattern, names):
    """The names a reader's ``pattern`` finds — in as much of each as
    the benchmark keeps (a kernel with three long results can push its
    ``custom-call(`` past that: PR 26's first chip run read nothing)."""
    rx, n = re.compile(pattern), _name_chars()
    return [name for name in names if rx.search(name[:n])]


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


@pytest.mark.parametrize("t,hq,window", [
    (4096, 64, 512), (4096, 48, None), (512, 64, 512), (2048, 48, None)],
    ids=["window_t4096", "full_t4096", "window_one_block", "full_t2048"])
def test_flash_prefill_compiles_at_the_code_context_cell_s_widths(
        one_chip, chip_paths, t, hq, window):
    """``laguna-xs.2.closed-code-context``'s admissions: 64 (window 512)
    or 48 (full) query heads on 8 K/V heads of 128, bf16, at its longest
    bucket and its shortest on the kernel route: one Mosaic kernel a
    call, named ``%flash_fwd`` as ``flash_forward_roofline`` finds it,
    and no lse output (one result)."""
    q = jax.ShapeDtypeStruct((1, t, hq, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, t, 8, 128), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: kernels.flash_prefill(
        q, k, v, window=window)).lower(q, kv, kv).compile()
    _, ops = _trace_names(compiled)
    found = _matches(_metric_args("flash_forward_roofline")["pattern"], ops)
    assert len(found) == 1 and found[0].split(" = ")[1].startswith("bf16[")


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


def _server_operands(srv, one_chip, n_layers=None, n_blocks=None):
    """(emb_p, blk_stack, head_p, kc, vc, state) of ``srv`` as shapes
    on the described chip.  ``n_layers`` / ``n_blocks`` DESCRIBE a
    deeper stack and a larger pool than the server staged on the host:
    the programs take both from their operands' shapes (those of a
    stack of runs take the depth from the net: leave ``n_layers``)."""
    emb_p, blk_stack, head_p = srv._params
    L = n_layers or srv._kc.shape[0]
    nb = n_blocks or srv._kc.shape[1]
    pool = jax.ShapeDtypeStruct((L, nb) + srv._kc.shape[2:],
                                srv._kc.dtype, sharding=one_chip)
    if n_layers:
        blk_stack = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((L,) + np.shape(a)[1:], a.dtype,
                                           sharding=one_chip), blk_stack)
    else:
        blk_stack = _on_chip(blk_stack, one_chip)
    return (_on_chip(emb_p, one_chip), blk_stack,
            _on_chip(head_p, one_chip), pool, pool,
            _on_chip(srv._state, one_chip))


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
    mp.setattr(ssm_mod, "_interpret", lambda: False)
    mp.setattr(ssm_mod, "ssm_route", lambda: "pallas")
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
            pool = _server_operands(srv, one_chip)
            out["decode_scan"] = _trace_names(
                srv._decode_scan(2, False).lower(*pool).compile())
            # operands as GenerationServer._admit packs them: a
            # 33-token prompt padded to the 64 bucket, then the same
            # prompt again with its two full blocks cached
            rows = 2 * srv.max_blocks       # the two table rows
            miss = S((_ADMIT_HEAD + 64 + 4 + rows,))
            out["admit_miss"] = _trace_names(
                srv._admit_miss_fn(64).lower(*pool, miss).compile())
            out["admit_hit"] = _trace_names(srv._admit_hit_fn(16, 2).lower(
                *pool, S((_ADMIT_HEAD + 16 + 2 + 1 + rows,))).compile())
        finally:
            srv.shutdown(drain=False, timeout=30.0)

        # a stack of runs: Mamba x 2, attention (2 query heads on 1 K/V
        # head of 128), Mamba -- per-slot recurrent state beside the pool
        from deeplearning4j_tpu.zoo.hybrid_decoder import HybridDecoder
        hybrid = MultiLayerNetwork(HybridDecoder(
            vocab_size=128, d_model=256, n_layers=4, d_ff=512, n_heads=2,
            n_kv_heads=1, attn_period=4, attn_offset=2, seq_len=64,
            dtype="bfloat16").conf()).init()
        srv = GenerationServer(hybrid, n_slots=8, max_len=64, block_size=16,
                               tick_batch=2, compute_dtype="bfloat16",
                               prefix_cache=False)
        try:
            pool = _server_operands(srv, one_chip)
            out["hybrid_decode_scan"] = _trace_names(
                srv._decode_scan(2, False).lower(*pool).compile())
            out["hybrid_admit_miss"] = _trace_names(
                srv._admit_miss_fn(64).lower(*pool, miss).compile())
        finally:
            srv.shutdown(drain=False, timeout=30.0)

        # a stack of all four kinds of attention run: dense full, routed
        # window x 2, routed full, routed window (keys 192 wide beside
        # values 128, a sink on the window layers, 4 of 16 experts held)
        from deeplearning4j_tpu.zoo import SparseWindowDecoder
        mp.setattr(expert_mod, "_interpret", lambda: False)
        mp.setattr(expert_mod, "expert_route", lambda: "pallas")
        sparse = MultiLayerNetwork(SparseWindowDecoder(
            vocab_size=128, d_model=256, layer_pattern=(0, 1, 1, 0, 1),
            routed_layers=(0, 1, 1, 1, 1), n_heads=4, n_kv_heads=1,
            window_kv_heads=2, qk_dim=192, v_dim=128, rotary_dim=64,
            window=16, d_ff=512, expert_ff=128, n_experts=16, top_k=2,
            held=(4, 4), seq_len=64, dtype="bfloat16").conf()).init()
        srv = GenerationServer(sparse, n_slots=8, max_len=64, block_size=16,
                               tick_batch=2, compute_dtype="bfloat16",
                               prefix_cache=False)
        try:
            out["sparse_pools"] = (srv._kc.shape, srv._vc.shape,
                                   srv._state["win_k"].shape)
            ops = _on_chip((*srv._params, srv._kc, srv._vc, srv._state),
                           one_chip)
            scan = srv._decode_scan(2, False).lower(*ops)
            out["sparse_decode_scan"] = _trace_names(scan.compile())
            out["sparse_polled"] = scan.out_info[3]
            out["sparse_admit_miss"] = _trace_names(
                srv._admit_miss_fn(64).lower(*ops, miss).compile())
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
    assert {k: v[0] for k, v in named_programs.items()
            if not k.startswith("sparse_po")} == {
        "sparse_decode_scan": "jit_decode_scan(1)",
        "sparse_admit_miss": "jit_admit_miss(1)",
        "train_step": "jit_train_step(1)",
        "decode_scan": "jit_decode_scan(1)",
        "admit_miss": "jit_admit_miss(1)",
        "admit_hit": "jit_admit_hit(1)",
        "hybrid_decode_scan": "jit_decode_scan(1)",
        "hybrid_admit_miss": "jit_admit_miss(1)"}
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


def test_a_stack_of_runs_carries_both_kernels_names(named_programs):
    """The hybrid net's decode scan: ``%ssm_step`` once a Mamba run
    (two runs), the grouped ``%paged_attention`` once (one attention
    run), each found in the 160 characters the benchmark keeps; the
    stacked recurrent state is produced by that kernel alone -- no
    slice, copy or write-back of a layer's state."""
    _, scan = named_programs["hybrid_decode_scan"]
    for kernel, n in (("ssm_step", 2), ("paged_attention", 1)):
        assert len(_matches(rf"^%{kernel}[.\d]* = ", scan)) == n, kernel
    both = _metric_args("ssm_tick_device_ms")["per_events_of"]["pattern"]
    assert len(_matches(both, scan)) == 3
    state = r"f32\[(3,8|24),16,512\]"       # [layers, slots, 16, d_inner]
    made = [ln for ln in scan if re.match(rf"%[\w.\-]+ = \(?[^=]*{state}", ln)
            and not re.search(r" (parameter|get-tuple-element|bitcast|tuple|"
                              r"while|custom-call)\(", ln)]
    assert not made, made[:3]
    assert all(re.search(state, ln) for ln in _matches(r"^%ssm_step", scan))
    text = "\n".join(scan)
    assert "/ssm_update/" in text and "/paged_read/" in text
    # the admission is plain jax.lax (a sequential scan over time)
    _, admit = named_programs["hybrid_admit_miss"]
    assert not _matches(both, admit)


def _name_keyed_metrics():
    """Every file of benchmark/layer_metrics/ that finds its events by
    a pattern."""
    names = []
    for path in sorted(glob.glob(os.path.join(
            ROOT, "benchmark", "layer_metrics", "*.json"))):
        name = os.path.basename(path)[:-len(".json")]
        if "pattern" in _metric_args(name):
            names.append(name)
    return names


@pytest.mark.parametrize("name", _name_keyed_metrics())
def test_name_keyed_metric_matches_a_compiled_name(named_programs, name):
    """Each ``pattern`` (and ``per_events_of.pattern``) matches a
    module or an instruction of the programs compiled above: a rename
    in the package fails here, not silently on the chip."""
    programs = [v for k, v in named_programs.items()
                if not k.startswith("sparse_po")]
    modules = [m for m, _ in programs]
    ops = [ln for _, lines in programs for ln in lines]
    args = _metric_args(name)
    line = modules if args.get("line") == "XLA Modules" else ops
    assert _matches(args["pattern"], line), args["pattern"]
    if "per_events_of" in args:
        assert _matches(args["per_events_of"]["pattern"], ops)


# ---------------------------------------------------------------------------
# One layout for the KV pool from entry to exit (ISSUE 26).  Before it
# the decode tick sliced a layer's pool out, copied it into the
# kernel's layout and wrote it back, per layer per tick, and every
# program copied the whole pool at entry and exit: 79% + 5.5% of the
# device's time in ``bert-large-causal.closed-decode``.  Two causes,
# both read from the compiled program: an XLA scatter of single rows
# makes XLA hold the loop's pool token-major inside a block, and a
# TPU's DEFAULT layout for a large [.., 16, 64] array puts the block
# axis minor (``kernels.paged_pool_width``).  Carrying the pool whole
# through the layer scan with an XLA scatter compiles to a whole-pool
# copy per layer: this is the test that fails on it.
# ---------------------------------------------------------------------------
def _pool_producers(lines, pool_shape):
    """Instructions of a compiled program whose RESULT is the pool or
    one layer of it, but for the plumbing (parameters, tuple elements
    and bitcasts, which move nothing; a while, a tuple and the kernel's
    aliased outputs have tuple results and do not match)."""
    dims = ",".join(map(str, pool_shape))
    layer = ",".join(map(str, pool_shape[1:]))
    rx = re.compile(r"^%?\S+ = \w+\[(?:" + dims + "|" + layer
                    + r")\]\S* ([\w-]+)\(")
    found = []
    for ln in lines:
        m = rx.match(ln)
        if m and m.group(1) not in ("parameter", "get-tuple-element",
                                    "bitcast"):
            found.append((m.group(1), ln[:160]))
    return found


def test_decode_scan_never_copies_or_slices_the_pool(named_programs):
    """``jit_decode_scan`` on the kernel route, tiny: the pool goes
    from parameter to result through the loops and the kernel's
    aliased operands alone."""
    _, lines = named_programs["decode_scan"]
    pool = re.search(r"^%kc\S* = \w+\[([\d,]+)\]\S* parameter\(",
                     "\n".join(lines), re.M).group(1)
    assert _pool_producers(lines, tuple(map(int, pool.split(",")))) == []


@pytest.fixture(scope="module")
def benchmark_geometry_programs(one_chip):
    """{program: instruction lines} of the decode scan (K = 8) and the
    miss admission (128-token bucket) of
    ``bert-large-causal.closed-decode``: 64 slots, 32-entry tables, a
    pool of 24 layers x 2049 blocks x 16 heads x 16 tokens, bf16 —
    SHAPES only: the server stages one layer at BERT-large's widths, a
    32-block pool and a 512-token vocabulary on the host."""
    from deeplearning4j_tpu.models.multi_layer_network import \
        MultiLayerNetwork
    from deeplearning4j_tpu.parallel import GenerationServer
    from deeplearning4j_tpu.zoo.gpt import Gpt
    mp = pytest.MonkeyPatch()
    mp.setattr(flash_mod, "_interpret", lambda: False)
    mp.setattr(paged_mod, "_interpret", lambda: False)
    mp.setattr(paged_mod, "_route", lambda: "pallas")

    def S(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    out = {}
    try:
        gpt = MultiLayerNetwork(Gpt(
            n_layers=1, d_model=1024, n_heads=16, d_ff=4096,
            vocab_size=512, max_len=512, seq_len=512).conf()).init()
        srv = GenerationServer(gpt, n_slots=64, max_len=512, block_size=16,
                               tick_batch=8, kv_blocks=32,
                               compute_dtype="bfloat16")
        try:
            ops = _server_operands(srv, one_chip, n_layers=24,
                                   n_blocks=2049)
            out["pool"] = ops[3].shape
            scan = srv._decode_scan(8, False).lower(*ops)
            out["decode_scan"] = _trace_names(scan.compile())[1]
            out["decode_scan_out"] = scan.out_info
            admit = srv._admit_miss_fn(128).lower(
                *ops, S((_ADMIT_HEAD + 128 + 8 + 2 * srv.max_blocks,)))
            out["admit_miss"] = _trace_names(admit.compile())[1]
            out["operands"] = ops
        finally:
            srv.shutdown(drain=False, timeout=30.0)
    finally:
        mp.undo()
    return out


def test_decode_scan_at_the_benchmark_geometry_holds_one_pool_layout(
        benchmark_geometry_programs):
    """A 24 x 2049 x 16 x 16 x 64 array's default layout on the chip
    is NOT the kernel's (dh = 64 < 128 lanes: the block axis goes
    minor), so the kernel route's pool is 128 wide -- two of the 16
    heads side by side in a row, 8 pool heads (ISSUE 34): no ``copy`` at
    entry or exit, nothing pool-shaped produced in between."""
    lines = benchmark_geometry_programs["decode_scan"]
    pool = benchmark_geometry_programs["pool"]
    assert pool == (24, 2049, 8, 16, 128)
    assert _pool_producers(lines, pool) == []
    # parameter, loops, kernel and result in ONE layout
    assert set(re.findall(r"bf16\[24,2049,8,16,128\]\{([\d,]+):T",
                          "\n".join(lines))) == {"4,3,2,1,0"}
    # the kernel takes both pools whole, as 8 wide heads a block, and
    # two query rows a pool head; its result is 128 wide a query head
    kernel, = _matches(r"^%paged_attention[.\d]* = .*custom-call\(", lines)
    assert kernel.count("bf16[393408,16,128]") >= 2
    assert "bf16[64,8,2,128]" in "\n".join(lines)
    # the two name-keyed metrics find the kernel in what the benchmark
    # keeps of its name, at the cell's own shapes
    for name in ("paged_attention_roofline", "decode_scan_tick_device_ms"):
        args = _metric_args(name)
        pattern = args.get("per_events_of", args)["pattern"]
        assert len(_matches(pattern, lines)) == 1, name


def test_grouped_decode_kernel_compiles_at_the_reasoning_cell_s_geometry(
        one_chip, chip_paths):
    """``jamba2-3b.closed-reasoning``'s read: 256 slots, 20 query heads
    on ONE K/V head of 128, 8-entry tables of 128-token blocks, a pool
    of 2 layers x 2,049 blocks, bf16 — the walk is four blocks a chunk
    there, through eight buffers (``paged_walk_blocks``).  The pool goes through the kernel
    aliased, in one layout, and the name-keyed metrics find the kernel
    in what the benchmark keeps of its name."""
    B, hq, h, dh, bs, mb = 256, 20, 1, 128, 128, 8
    pool_shape = (2, 2049, h, bs, 128)
    assert paged_mod.paged_walk_blocks(bs, h, 128, jnp.bfloat16, mb) == (4, 8)

    def S(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = S(pool_shape, jnp.bfloat16)
    compiled = jax.jit(
        lambda q, kn, vn, kp, vp, t, p, wb, wo, lay:
        paged_mod._paged_decode_write_pallas(q, kn, vn, kp, vp, t, p, wb,
                                             wo, lay, dh ** -0.5),
        donate_argnums=(3, 4)).lower(
        S((B, hq, dh), jnp.bfloat16), S((B, h, dh), jnp.bfloat16),
        S((B, h, dh), jnp.bfloat16), pool, pool, S((B, mb)), S((B,)),
        S((B,)), S((B,)), S(())).compile()
    _, lines = _trace_names(compiled)
    assert _pool_producers(lines, pool_shape) == []
    for name in ("paged_attention_roofline", "ssm_tick_device_ms"):
        args = _metric_args(name)
        pattern = args.get("per_events_of", args)["pattern"]
        assert len(_matches(pattern, lines)) == 1, name
    kernel, = _matches(r"^%paged_attention[.\d]* = .*custom-call\(", lines)
    assert kernel.count("bf16[4098,128,128]") >= 2      # both pools, whole


def test_admission_at_the_benchmark_geometry_scatters_in_place(
        benchmark_geometry_programs):
    """``jit_admit_miss`` paid the same entry and exit copies (39 ms an
    admission, PERF.md PR 25): at lane width its block scatter is the
    pool's one producer, in place."""
    found = _pool_producers(benchmark_geometry_programs["admit_miss"],
                            benchmark_geometry_programs["pool"])
    assert sorted(op for op, _ in found) == ["fusion", "fusion",
                                             "scatter", "scatter"], found


def test_each_program_meets_the_host_through_one_array(
        benchmark_geometry_programs):
    """ISSUE 32, at cell 2's shapes for the described chip: beside the
    pools and the state ``jit_decode_scan`` (K = 8) returns ONE array,
    int32 [64, K + 2] — the staged tokens, ``emitted``, ``remaining``:
    all the scheduler reads of a scan — and ``jit_admit_miss`` takes
    ONE operand beyond parameters, pools and state, the int32 vector
    ``_pack_admission`` builds; the compiled entry has no parameter
    more."""
    kc, vc, state, polled = benchmark_geometry_programs["decode_scan_out"]
    assert (polled.shape, polled.dtype) == ((64, 8 + 2), jnp.int32)
    assert isinstance(state, dict) and kc.shape == vc.shape
    n_device = len(jax.tree_util.tree_leaves(
        benchmark_geometry_programs["operands"]))
    entry = [ln for ln in benchmark_geometry_programs["admit_miss"]
             if re.search(r" parameter\(\d+\)", ln)]
    # the entry computation's parameters come first in the text, numbered
    # from 0: one beyond the device-resident operands, the packed vector
    numbers = [int(re.search(r" parameter\((\d+)\)", ln).group(1))
               for ln in entry]
    assert max(numbers) == n_device, (max(numbers), n_device)
    packed = [ln for ln in entry if f" parameter({n_device})" in ln]
    assert any(re.search(r"= s32\[\d+\]", ln) for ln in packed), packed


# ---------------------------------------------------------------------------
# ISSUE 33: keys wider than values, a sink, a window's one-block table,
# the held experts' kernel -- and the accepted cells' kernel unmoved
# ---------------------------------------------------------------------------
def _decode_write_lowered(one_chip, B, hq, h, dk, dv, bs, mb, L, nb, sink,
                          ring=False):
    """``_paged_decode_write_pallas`` lowered at a cell's geometry: bf16
    pools of whole 128-lane rows, K and V each at its own width; with
    ``ring`` the table is a window's ring over ``mb`` blocks and the
    call says which of them the row lands in."""
    def S(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    wide = lambda d: -(-d // 128) * 128
    bf = jnp.bfloat16
    args = [S((B, hq, dk), bf), S((B, h, dk), bf), S((B, h, dv), bf),
            S((L, nb, h, bs, wide(dk)), bf), S((L, nb, h, bs, wide(dv)), bf),
            S((B, mb)), S((B,)), S((B,)), S((B,)), S(())]
    args.append(S((hq,), jnp.float32) if sink else None)
    args.append(S((B,)) if ring else None)
    return jax.jit(
        lambda q, kn, vn, kp, vp, t, p, wb, wo, lay, sk, wat:
        paged_mod._paged_decode_write_pallas(q, kn, vn, kp, vp, t, p, wb, wo,
                                             lay, dk ** -0.5, sk, wat),
        donate_argnums=(3, 4)).lower(*args)


@pytest.mark.parametrize("kind", ["full", "window"])
def test_paged_kernel_compiles_at_the_sparse_window_cell_s_geometry(
        one_chip, chip_paths, kind):
    """``mimo-v2-flash.closed-long-reasoning``'s two reads, 256 slots,
    64 query heads, keys 192 wide in 256-lane rows beside values 128
    wide, bf16: a FULL layer's (4 K/V heads, 16-entry tables of
    128-token blocks, 2 layers x 4,097 blocks) and a WINDOW layer's (8
    K/V heads, the slot's one block, a sink a query head, 5 layers x 257
    blocks).  Both pools go through the kernel aliased, each in one
    layout, and the name-keyed metrics find the kernel."""
    h, mb, L, nb, sink = {"full": (4, 16, 2, 4097, False),
                          "window": (8, 1, 5, 257, True)}[kind]
    assert paged_mod.paged_walk_blocks(128, h, 256, jnp.bfloat16, mb, 128)[0] == 1
    compiled = _decode_write_lowered(one_chip, 256, 64, h, 192, 128, 128, mb,
                                     L, nb, sink).compile()
    _, lines = _trace_names(compiled)
    for width in (256, 128):
        assert _pool_producers(lines, (L, nb, h, 128, width)) == []
    for name in ("paged_attention_roofline", "decode_scan_tick_device_ms"):
        args = _metric_args(name)
        pattern = args.get("per_events_of", args)["pattern"]
        assert len(_matches(pattern, lines)) == 1, name
    kernel, = _matches(r"^%paged_attention[.\d]* = .*custom-call\(", lines)
    for width in (256, 128):                       # both pools, whole
        assert f"bf16[{L * nb * h},128,{width}]" in kernel


@pytest.mark.parametrize("rows", [256, 512], ids=["decode_tick", "prefill"])
def test_expert_ffn_compiles_at_the_sparse_window_cell_s_geometry(
        one_chip, chip_paths, rows):
    """16 held experts of 4096 x 2048 read out of a run's stacked [4, 16,
    ., .] matrices by a layer index, top-8 picks of 256 rows (a decode
    tick) or 512 (the largest prefill bucket), bf16: the chip's compiler
    takes the weight tiles' VMEM and the aligned row tiles, the stacked
    matrices reach the kernel WHOLE (no slice of a layer is made), and
    ``expert_ffn_roofline`` finds the kernel by name."""
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    bf = jnp.bfloat16
    d, ff, held, k, n = 4096, 2048, 16, 8, 4
    compiled = jax.jit(
        lambda x, e, w, wg, wu, wd, lay: expert_mod._expert_ffn_call(
            x, e, w, wg, wu, wd, lay, interpret=False)).lower(
        S((rows, d), bf), S((rows, k), jnp.int32), S((rows, k), jnp.float32),
        S((n, held, d, ff), bf), S((n, held, d, ff), bf),
        S((n, held, ff, d), bf), S((), jnp.int32)).compile()
    _, lines = _trace_names(compiled)
    pattern = _metric_args("expert_ffn_roofline")["pattern"]
    kernel, = _matches(pattern, lines)
    assert kernel.count(f"bf16[{n},{held},{d},{ff}]") == 2      # gate, up
    assert f"bf16[{n},{held},{ff},{d}]" in kernel
    produced = [ln for ln in lines
                if re.match(rf"%\S+ = bf16\[{held},({d},{ff}|{ff},{d})\]", ln)]
    assert produced == []


#: the decode kernel's Mosaic body at the accepted cells' geometries:
#: (operations, sha256 of their text without source locations).  Cell 3
#: as the parent of ISSUE 33 lowered it and both of cell 4's kinds as
#: the parent of ISSUE 34 did, all three unmoved by ISSUE 34; cell 2 as
#: ISSUE 34 made it, the kernel handed a pool of two heads a row: 8
#: pool heads 128 wide, 2 query rows on each.  A new jax may move
#: operations and hash together; a change to the kernel that moves
#: them alone moved those cells.
_KERNEL_BODIES = {
    "bert-large-causal.closed-decode": (
        (64, 16, 8, 128, 128, 16, 32, 24, 2049, False),
        699, "9f6017e060606b0d"),
    "jamba2-3b.closed-reasoning": (
        (256, 20, 1, 128, 128, 128, 8, 2, 2049, False),
        700, "629d03e37915c0f8"),
    "mimo-v2-flash.closed-long-reasoning/full": (
        (256, 64, 4, 192, 128, 128, 16, 2, 4097, False),
        704, "044cb7d6437fbd46"),
    "mimo-v2-flash.closed-long-reasoning/window": (
        (256, 64, 8, 192, 128, 128, 1, 5, 257, True),
        714, "dfd8036e49fa9a28")}


@pytest.mark.parametrize("cell", sorted(_KERNEL_BODIES))
def test_equal_widths_and_no_sink_lower_the_kernel_the_accepted_cells_had(
        one_chip, chip_paths, cell):
    """The decode kernel is the program it was, operation for
    operation: with keys as wide as values and no sink, before it
    learnt either; at every geometry, before pools held several heads a
    row (which is the caller's layout, not the kernel's)."""
    import base64
    import hashlib
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    geometry, n_ops, digest = _KERNEL_BODIES[cell]
    text = _decode_write_lowered(one_chip, *geometry).as_text()
    body = re.search(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text).group(1)
    ctx = jax_mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        ops = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False)
    assert len(ops.splitlines()) == n_ops
    assert hashlib.sha256(ops.encode()).hexdigest()[:16] == digest


def test_decode_scan_at_the_benchmark_geometry_keeps_its_instruction_count(
        benchmark_geometry_programs):
    """Cell 2's ``jit_decode_scan`` (K = 8) for the described chip: 833
    instructions.  It had 818 before the pool held two heads a row, and
    through ISSUE 33 (the generator learnt kinds of pool, window rings
    and a routed tally there; a stack that keeps none of those carries
    none of them).  ISSUE 34's 15, all around the kernel's call in the
    layer scan's body: the query rows' select against the lanes'
    constant mask and the result's select + sum over a pool head's two
    rows, with their operands (+2 select, +1 reduce, +1 add, +5
    broadcast, +1 constant, +4 parameter, +3 get-tuple-element, +2
    bitcast), less what a head a row cost (-2 pad of the new rows to
    the lane width, -2 reshape: q and the result no longer change
    lanes on their way between [64, 1024] and the kernel)."""
    lines = [ln for ln in benchmark_geometry_programs["decode_scan"]
             if " = " in ln]
    assert len(lines) == 833


def test_a_sparse_window_stack_carries_its_kernels_names(named_programs):
    """A tiny stack of all four kinds of run lowered for the described
    chip: K pools 256 lanes wide beside V pools of 128; its decode scan
    calls ``%paged_attention`` once a run's layer scan (4) and
    ``%expert_ffn`` once a routed run's (3); beside pools and state it
    returns ONE array, the slots' rows and the routed tally's below
    them; its admission runs the same expert kernel."""
    kc, vc, win_k = named_programs["sparse_pools"]
    assert kc[2:] == (1, 16, 256) and vc[2:] == (1, 16, 128)
    assert win_k == (3, 9, 2, 16, 256)
    _, lines = named_programs["sparse_decode_scan"]
    expert = _metric_args("expert_ffn_roofline")["pattern"]
    assert len(_matches(r"^%paged_attention[.\d]* = ", lines)) == 4
    assert len(_matches(expert, lines)) == 3
    polled = named_programs["sparse_polled"]
    assert (polled.shape, polled.dtype) == ((8 + 2, 2 + 2), jnp.int32)
    _, lines = named_programs["sparse_admit_miss"]
    assert len(_matches(expert, lines)) == 3


# ---------------------------------------------------------------------------
# laguna-xs.2.closed-code-context (ISSUE 36): 128 slots of 4,096, 48 / 64
# query heads on 8 K/V heads of 128, a 512-wide window ring over four
# 128-position blocks, 256 held experts of 2048 x 512
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["full", "ring"])
def test_paged_kernel_compiles_at_the_code_context_cell_s_geometry(
        one_chip, chip_paths, kind):
    """A FULL layer's read (48 query heads, 6 a K/V head, 32-entry tables,
    2 layers x 4,097 blocks) and a SLIDING layer's (64 query heads, the
    slot's ring of FOUR blocks, 3 layers x 513 blocks): the ring's call
    carries one more scalar operand, the table entry the row lands in,
    and both pools still go through the kernel aliased, in one layout."""
    hq, mb, L, nb, ring = {"full": (48, 32, 2, 4097, False),
                           "ring": (64, 4, 3, 513, True)}[kind]
    assert paged_mod.paged_walk_blocks(128, 8, 128, jnp.bfloat16, mb, 128) \
        == (1, 5)
    lowered = _decode_write_lowered(one_chip, 128, hq, 8, 128, 128, 128, mb,
                                    L, nb, False, ring)
    _, lines = _trace_names(lowered.compile())
    assert _pool_producers(lines, (L, nb, 8, 128, 128)) == []
    kernel, = _matches(r"^%paged_attention[.\d]* = .*custom-call\(", lines)
    assert kernel.count(f"bf16[{L * nb * 8},128,128]") >= 2
    for name in ("paged_attention_roofline", "decode_scan_tick_device_ms"):
        args = _metric_args(name)
        pattern = args.get("per_events_of", args)["pattern"]
        assert len(_matches(pattern, lines)) == 1, name


@pytest.mark.parametrize("rows", [128, 4096], ids=["decode_tick", "prefill"])
def test_expert_ffn_compiles_at_the_code_context_cell_s_geometry(
        one_chip, chip_paths, rows):
    """256 HELD experts of 2048 x 512 (one fetch a matrix an expert) read
    out of a run's stacked [3, 256, ., .] matrices by a layer index, top-8
    picks of 128 rows (a decode tick: 4 rows an expert) or 4,096 (the
    largest prefill bucket): the stacked matrices reach the kernel WHOLE
    and ``expert_ffn_roofline`` finds the kernel by name."""
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    bf = jnp.bfloat16
    d, ff, held, k, n = 2048, 512, 256, 8, 3
    assert expert_mod._ff_tile(d, ff, 2) == ff
    compiled = jax.jit(
        lambda x, e, w, wg, wu, wd, lay: expert_mod._expert_ffn_call(
            x, e, w, wg, wu, wd, lay, interpret=False)).lower(
        S((rows, d), bf), S((rows, k), jnp.int32), S((rows, k), jnp.float32),
        S((n, held, d, ff), bf), S((n, held, d, ff), bf),
        S((n, held, ff, d), bf), S((), jnp.int32)).compile()
    _, lines = _trace_names(compiled)
    kernel, = _matches(_metric_args("expert_ffn_roofline")["pattern"], lines)
    assert kernel.count(f"bf16[{n},{held},{d},{ff}]") == 2      # gate, up
    assert f"bf16[{n},{held},{ff},{d}]" in kernel
    produced = [ln for ln in lines
                if re.match(rf"%\S+ = bf16\[{held},({d},{ff}|{ff},{d})\]", ln)]
    assert produced == []


@pytest.fixture(scope="module")
def code_context_programs(one_chip):
    """``laguna-xs.2.closed-code-context``'s decode scan (K = 8) and its
    1,024-token admission lowered and compiled for the described chip at
    the cell's own shapes.  SHAPES only: the host stages 8 of the 256
    experts, a 32-block pool and a 512-row vocabulary; the runs are then
    told they hold every expert and the operands DESCRIBE the stacked
    [., 256, ., .] matrices, the 4,097-block pool and the 100,352-row
    table and head."""
    from deeplearning4j_tpu.models.multi_layer_network import \
        MultiLayerNetwork
    from deeplearning4j_tpu.parallel import GenerationServer
    from deeplearning4j_tpu.zoo import SparseWindowDecoder
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-xs.2.json")) as f:
        ctor = json.load(f)["ctor"]
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "laguna-xs.2.closed-code-context.json")) as f:
        server = json.load(f)["server"]
    vocab, experts = ctor["vocab_size"], ctor["n_experts"]
    mp = pytest.MonkeyPatch()
    for mod in (paged_mod, expert_mod):
        mp.setattr(mod, "_interpret", lambda: False)
    mp.setattr(paged_mod, "_route", lambda: "pallas")
    mp.setattr(expert_mod, "expert_route", lambda: "pallas")
    out = {}
    try:
        net = MultiLayerNetwork(SparseWindowDecoder(
            **{**ctor, "vocab_size": 512, "held": (0, 8)}).conf()).init()
        net.layers[0].n_in = net.layers[-1].n_out = vocab
        for run in net.layers[1:-1]:
            run.held = None
        srv = GenerationServer(net, kv_blocks=32, **server)
        try:
            def described(path, a):
                shape = np.shape(a)
                if path[-1].key in ("W_gate", "W_up", "W_down") \
                        and len(shape) == 4:
                    shape = (shape[0], experts) + shape[2:]
                elif shape[:1] == (512,):               # table, head
                    shape = (vocab,) + shape[1:]
                return jax.ShapeDtypeStruct(shape, a.dtype,
                                            sharding=one_chip)
            params = jax.tree_util.tree_map_with_path(described, srv._params)
            pool = jax.ShapeDtypeStruct((2, 4097) + srv._kc.shape[2:],
                                        srv._kc.dtype, sharding=one_chip)
            ops = (*params, pool, pool, _on_chip(srv._state, one_chip))
            out["state"] = {k: (v.shape, v.dtype)
                            for k, v in srv._state.items()}
            scan = srv._decode_scan(8, False).lower(*ops)
            out["decode_scan"] = _trace_names(scan.compile())
            out["polled"] = scan.out_info[3]
            miss = jax.ShapeDtypeStruct(
                (_ADMIT_HEAD + 1024 + 8 + 2 * srv.max_blocks,), jnp.int32,
                sharding=one_chip)
            out["admit_miss"] = _trace_names(
                srv._admit_miss_fn(1024).lower(*ops, miss).compile())
        finally:
            srv.shutdown(drain=False, timeout=30.0)
    finally:
        mp.undo()
    return out


def test_the_code_context_cell_s_programs_compile_for_v5e(
        code_context_programs):
    """Both programs fit the chip and keep their names; a tick calls
    ``%paged_attention`` once a run (dense full, sliding x 3, routed
    full) and ``%expert_ffn`` once a routed run, over pools and rings in
    the kernel's layout alone; the scan returns ONE array, the slots'
    rows and, below them, the [257] tally and the reached count; ``attn_gate`` and
    ``expert_shared`` name their fusions in the HLO."""
    state = code_context_programs["state"]
    assert state["win_k"][0] == (3, 128 * 4 + 1, 8, 128, 128)
    assert state["routed"][0] == (256 + 1,) and state["reached"][0] == (1,)
    assert state["logits"][0] == (128, 100352)
    name, lines = code_context_programs["decode_scan"]
    assert name == "jit_decode_scan(1)"
    assert len(_matches(r"^%paged_attention[.\d]* = ", lines)) == 3
    expert = _metric_args("expert_ffn_roofline")["pattern"]
    assert len(_matches(expert, lines)) == 2
    for pool in ((2, 4097, 8, 128, 128), (3, 513, 8, 128, 128)):
        assert _pool_producers(lines, pool) == []
    text = "\n".join(lines)
    assert "/attn_gate/" in text and "/expert_shared/" in text
    polled = code_context_programs["polled"]
    assert (polled.shape, polled.dtype) == ((128 + 26, 8 + 2), jnp.int32)
    name, lines = code_context_programs["admit_miss"]
    assert name == "jit_admit_miss(1)"
    assert len(_matches(expert, lines)) == 2
