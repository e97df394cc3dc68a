"""Pipeline parallelism — GPipe microbatching over a 'pipe' mesh axis.

The LAST parallelism axis from SURVEY §2.3 ("absent in the reference;
design the trainer so stages are expressible later").  TPU-native
design: stages are expressed as SPMD — every device runs the SAME
program under ``shard_map``; the stage's parameter slice arrives via a
``P('pipe')``-sharded leading axis, microbatch activations rotate
around the ring with ``lax.ppermute``, and the whole schedule is a
``lax.scan`` (compiler-friendly: one compiled step, no per-stage
Python).  Backward is ``jax.grad`` THROUGH the scheduled forward —
scan+ppermute are differentiable, so the GPipe backward pass (reverse
schedule with re-rotated cotangents) falls out of autodiff instead of
being hand-built.

Scope: homogeneous stacks (N identical blocks, e.g.
``TransformerEncoderBlock``) — the case pipeline parallelism exists
for.  N must divide by the pipe-axis size; each stage owns N/S
consecutive blocks and scans over them locally.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import telemetry

# One series answers "how much of the schedule is bubble" whichever
# driver built it — ShardedTrainer's pipelined path imports this
# family rather than redefining it.
_PIPE_BUBBLE = telemetry.gauge(
    "pipeline_bubble_fraction",
    "(S-1)/(S-1+n_micro) idle fraction of the GPipe schedule")
_PIPE_STEPS = telemetry.counter(
    "pipeline_steps_total", "PipelinedTransformerLM optimizer steps",
    labelnames=("worker",))


def _pipe_varying_zeros(like, axis):
    """Zeros with the scan-carry type of a post-``ppermute`` value:
    the carry must be pre-cast to pipe-varying."""
    return lax.pcast(jnp.zeros_like(like), (axis,), to="varying")


def stack_block_params(block_conf, n_blocks: int, key,
                       dtype=jnp.float32):
    """Init n_blocks independent parameter sets and stack each leaf on
    a leading axis — the array layout the pipe axis shards."""
    keys = jax.random.split(key, n_blocks)
    trees = [block_conf.init(k, dtype)[0] for k in keys]
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *trees)


def pipe_axis_name(mesh: Mesh) -> str:
    """Canonical pipe-axis lookup: 'pipe' (pipeline.py's historical
    name) or MeshConfig's 'pipeline'."""
    for name in ("pipe", "pipeline"):
        if name in mesh.shape:
            return name
    raise ValueError(f"mesh {mesh.shape} has no pipe/pipeline axis")


def gpipe_apply(mesh: Mesh, stacked_params, x, block_apply: Callable,
                n_micro: int, axis: Optional[str] = None,
                data_axis: Optional[str] = None):
    """Run x [B, ...] through the stacked blocks with a GPipe schedule.

    ``block_apply(params_one_block, activations) -> activations`` is
    the per-block forward.  ``n_micro`` microbatches must divide the
    PER-DATA-SHARD batch; the bubble fraction is
    (S-1)/(S-1+n_micro).  Returns [B, ...] with the pipeline semantics
    IDENTICAL to applying the blocks sequentially.

    ``data_axis`` composes DP x PP (round-3 review weak 4): x arrives
    batch-sharded over that axis, every data group runs its own
    pipeline over its local microbatches, and gradient all-reduce over
    'data' falls out of autodiff through shard_map."""
    axis = axis or pipe_axis_name(mesh)
    S = mesh.shape[axis]
    B = x.shape[0]
    d_sz = mesh.shape[data_axis] if data_axis else 1
    n_blocks = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n_blocks % S:
        raise ValueError(f"{n_blocks} blocks do not divide over "
                         f"{S} pipeline stages")
    if B % (n_micro * d_sz):
        raise ValueError(f"batch {B} must divide into {n_micro} "
                         f"microbatches x {d_sz} data shards")

    def apply_stage(params_local, h):
        def body(carry, p):
            return block_apply(p, carry), None
        out, _ = lax.scan(body, h, params_local)
        return out

    def worker(params_local, x_local, stage_id):
        xm = x_local.reshape((n_micro, x_local.shape[0] // n_micro)
                             + x_local.shape[1:])
        # stage index arrives as pipe-sharded DATA rather than
        # lax.axis_index: axis_index lowers to a PartitionId
        # instruction that GSPMD refuses to partition when non-manual
        # (auto) axes remain — e.g. the DP x TP x PP composition
        idx = stage_id[0]
        # the scan carry becomes pipe-varying after the first ppermute;
        # pre-cast the zeros so the carry type is stable across ticks
        state = _pipe_varying_zeros(xm[0], axis)

        def tick(state, t):
            # stage 0 ingests microbatch t (clamped: late ticks feed
            # garbage that never reaches the collected outputs)
            inject = xm[jnp.clip(t, 0, n_micro - 1)]
            h = jnp.where(idx == 0, inject, state)
            y = apply_stage(params_local, h)
            nxt = lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)])
            return nxt, y

        _, ys = lax.scan(tick, state, jnp.arange(S + n_micro - 1))
        # microbatch m leaves the LAST stage at tick (S-1) + m
        outs = lax.dynamic_slice_in_dim(ys, S - 1, n_micro, axis=0)
        # where, NOT outs*mask: bubble-tick garbage on non-last stages
        # may be non-finite and 0*NaN would poison the psum
        outs = jnp.where(idx == S - 1, outs, jnp.zeros_like(outs))
        # replicate the last stage's outputs to every device
        outs = lax.psum(outs, axis)
        return outs.reshape((outs.shape[0] * outs.shape[1],)
                            + outs.shape[2:])

    x_spec = P(data_axis) if data_axis else P()
    # only the pipe (and data) axes are MANUAL; any other mesh axis
    # ('model', 'sequence') stays auto-partitioned, so GSPMD places
    # tensor-parallel collectives INSIDE the stage body from the
    # operands' shardings — this is what lets DP x TP x PP compose
    # through one shard_map (round-4 review item 7)
    manual = {axis} | ({data_axis} if data_axis else set())
    out = jax.shard_map(
        worker, mesh=mesh,
        in_specs=(P(axis), x_spec, P(axis)), out_specs=x_spec,
        axis_names=frozenset(manual))(stacked_params, x, jnp.arange(S))
    return out


class PipelinedTransformerLM:
    """Pipelined model trained through a normal fit path: replicated
    embedding + N pipelined ``TransformerEncoderBlock``s + replicated
    head, one jitted step over the mesh.  Composes DP x PP when the
    mesh carries a 'data' axis (round-3 review weak 4: a trainer feature,
    not a demo) — batch sharded over 'data', block stack sharded over
    the pipe axis, gradient all-reduce by GSPMD/shard_map autodiff."""

    @classmethod
    def from_mesh_config(cls, mesh_conf, devices=None, **kw):
        """Build from a ``MeshConfig(data=..., pipeline=...)`` — the
        same mesh vocabulary as ``ShardedTrainer``."""
        return cls(mesh=mesh_conf.build(devices), **kw)

    def __init__(self, vocab_size: int, d_model: int, n_blocks: int,
                 n_heads: int, d_ff: int, seq_len: int, n_classes: int,
                 mesh: Mesh, n_micro: int = 4, lr: float = 1e-3,
                 seed: int = 0):
        from deeplearning4j_tpu.nn.conf.layers_transformer import (
            EmbeddingSequenceLayer, TransformerEncoderBlock)
        from deeplearning4j_tpu.optimize.updaters import Adam

        self.mesh, self.n_micro = mesh, n_micro
        self._pipe_axis = pipe_axis_name(mesh)
        self._data_axis = ("data" if "data" in mesh.shape
                           and mesh.shape["data"] > 1 else None)
        self.block_conf = TransformerEncoderBlock(
            n_heads=n_heads, d_ff=d_ff, use_flash=False)
        self.block_conf.infer_shapes((seq_len, d_model))
        emb = EmbeddingSequenceLayer(n_in=vocab_size, n_out=d_model,
                                     max_len=seq_len)
        emb.infer_shapes((seq_len,))
        self.emb_conf = emb
        k = jax.random.key(seed)
        k_emb, k_blocks, k_head = jax.random.split(k, 3)
        emb_params, _ = emb.init(k_emb)
        head_w = 0.02 * jax.random.normal(k_head, (d_model, n_classes))
        self.params = {
            "emb": emb_params,
            "blocks": stack_block_params(self.block_conf, n_blocks,
                                         k_blocks),
            "head": {"W": head_w,
                     "b": jnp.zeros((n_classes,), jnp.float32)},
        }
        # place params on the pipe axis BEFORE building optimizer state:
        # zeros_like then inherits the shardings, so Adam's m/v for the
        # stacked blocks are born sharded (the memory PP exists for)
        spec = jax.tree_util.tree_map(lambda a: P(), self.params)
        spec["blocks"] = jax.tree_util.tree_map(
            lambda a: P(self._pipe_axis), self.params["blocks"])
        self.params = jax.device_put(
            self.params, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), spec))
        self._updater = Adam(learning_rate=lr)
        self.opt_state = self._updater.init_state(self.params)
        block_conf, emb_conf = self.block_conf, self.emb_conf
        n_mi = n_micro
        msh = mesh

        p_axis, d_axis = self._pipe_axis, self._data_axis

        def forward(params, ids):
            h, _ = emb_conf.apply(params["emb"], {}, ids,
                                  training=False)
            h = gpipe_apply(
                msh, params["blocks"], h,
                lambda p, a: block_conf.apply(p, {}, a,
                                              training=False)[0],
                n_mi, axis=p_axis, data_axis=d_axis)
            pooled = jnp.mean(h, axis=1)
            return pooled @ params["head"]["W"] + params["head"]["b"]

        def loss_fn(params, ids, labels):
            logits = forward(params, ids)
            lp = jax.nn.log_softmax(logits, -1)
            return -jnp.mean(jnp.sum(labels * lp, -1))

        def train_step(params, opt_state, ids, labels, it):
            loss, grads = jax.value_and_grad(loss_fn)(params, ids,
                                                      labels)
            updates, opt_state = self._updater.update(grads, opt_state,
                                                      params, it)
            params = jax.tree_util.tree_map(lambda p, u: p - u, params,
                                            updates)
            opt_state = self._updater.finalize(opt_state, params)
            return params, opt_state, loss

        self._forward = jax.jit(forward)
        self._step = jax.jit(train_step)
        self._it = 0
        _PIPE_BUBBLE.set((mesh.shape[p_axis] - 1)
                         / (mesh.shape[p_axis] - 1 + n_micro))
        self._step_counter = _PIPE_STEPS.labels(
            worker=jax.process_index())

    def _shard_in(self, a):
        a = jnp.asarray(a)
        if self._data_axis is None:
            return a
        return jax.device_put(a, NamedSharding(
            self.mesh, P(*([self._data_axis] + [None] * (a.ndim - 1)))))

    def fit_batch(self, ids, labels):
        with telemetry.span("train/pipeline_step", iteration=self._it):
            self.params, self.opt_state, loss = self._step(
                self.params, self.opt_state, self._shard_in(ids),
                self._shard_in(labels), self._it)
            loss = float(loss)
        self._it += 1
        self._step_counter.inc()
        return loss

    def predict(self, ids):
        return np.asarray(self._forward(self.params,
                                        self._shard_in(ids)))
