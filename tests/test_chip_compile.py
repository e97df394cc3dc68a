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
import importlib
import os

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
