"""Sharded trainer: one jitted train step over a device mesh.

Replaces ``org.deeplearning4j.parallelism.ParallelWrapper`` (thread-per-GPU
replicas + averaging/EncodedGradientsAccumulator) and the Spark
``SharedTrainingMaster`` peer-to-peer Aeron gradient sharing with the
TPU-native design: parameters live sharded/replicated on the mesh per
``NamedSharding`` specs, the batch is split over the 'data' axis, and XLA's
GSPMD partitioner inserts the gradient all-reduce over ICI — there is no
gradient-compression codec because dense ICI all-reduce is faster than any
encode/decode (SURVEY.md §5.8).

Tensor parallelism (absent in the reference) falls out of the same
mechanism: Dense kernels whose output dim divides the 'model' axis are
sharded column-wise, the next layer row-wise, and GSPMD places the psum.
"""
from __future__ import annotations

import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.kernels import trace_mesh
from deeplearning4j_tpu.optimize.fit_loop import run_fit
from deeplearning4j_tpu.parallel.mesh import MeshConfig

log = logging.getLogger("deeplearning4j_tpu")

# Per-worker step counters: each jax.distributed process runs its own
# registry, labeled by process index; the driver folds worker snapshots
# with MetricsRegistry.merge_snapshot() (counters add across workers,
# so the merged sharded_steps_total{worker=...} series enumerate the
# fleet).  Collectives inside the jitted step are NOT host-visible —
# the dispatch span bounds them; per-op device time needs XProf.
# The bubble gauge family lives in pipeline.py (one definition, both
# GPipe drivers set it).
from deeplearning4j_tpu.parallel.pipeline import _PIPE_BUBBLE

_STEPS = telemetry.counter(
    "sharded_steps_total", "compiled mesh steps dispatched",
    labelnames=("worker",))

#: optimizer-step device-time sampling rate (ISSUE 13): 1-in-N steps
#: pays a block_until_ready so the dispatch-ahead pipeline keeps its
#: async overlap on the other N-1
_PROFILE_STEP_EVERY = 4


def _tp_shardable_layers(model) -> dict:
    """Per-layer tensor-parallel sharding rules: name -> {param: kind}
    with kind 'col' (P(None, 'model')) or 'row' (P('model', None)) —
    Megatron-style.  Dense 'W' shards column-wise; transformer blocks
    shard Wqkv/W1 column-wise and W2/Wo row-wise.  The FFN half gets
    the classic column-then-row pairing (one psum); the attention half
    shards Wqkv contiguously, which crosses the fused q/k/v slice
    boundaries — GSPMD keeps the math exact but regathers the qkv
    activation before the head split, so the attention half buys
    memory sharding at the cost of one extra activation gather (true
    Megatron interleaves per-head [q_h|k_h|v_h] kernel columns).
    Sequence embeddings shard over the vocab rows.  Recurrent
    fused-gate kernels ([in, 4h] — gate slices would cross shard
    boundaries) and conv HWIO kernels are EXCLUDED: they replicate, DP
    still shards their gradients' batch."""
    from deeplearning4j_tpu.nn.conf.layers_core import DenseLayer
    from deeplearning4j_tpu.nn.conf.layers_transformer import (
        EmbeddingSequenceLayer, TransformerEncoderBlock)
    rules = {}
    if hasattr(model, "layers"):
        items = ((f"layer_{i}", ly) for i, ly in enumerate(model.layers))
    else:
        items = ((n, s.layer) for n, s in model.conf.vertices.items()
                 if s.layer is not None)
    for name, ly in items:
        if isinstance(ly, TransformerEncoderBlock):
            rules[name] = {"Wqkv": "col", "W1": "col",
                           "W2": "row", "Wo": "row"}
        elif isinstance(ly, EmbeddingSequenceLayer):
            rules[name] = {"W": "row"}
        elif isinstance(ly, DenseLayer) and not getattr(ly, "IS_RNN",
                                                        False):
            rules[name] = {"W": "col"}
    return rules


def _param_spec(path, shape, tp: int, shardable: dict):
    """Sharding rule for one parameter leaf under tensor parallelism.
    `path` is a tree path whose second-to-last key is the owning
    layer/vertex name (works for both the params tree and optimizer-state
    trees that mirror it one level deeper)."""
    keys = [getattr(p, "key", str(p)) for p in path]
    layer = keys[-2] if len(keys) >= 2 else None
    kind = shardable.get(layer, {}).get(keys[-1]) if keys else None
    if tp > 1 and kind and len(shape) == 2:
        if kind == "col" and shape[-1] % tp == 0:
            return P(None, "model")
        if kind == "row" and shape[0] % tp == 0:
            return P("model", None)
    return P()


def _find_block_run(model):
    """Longest run of conf-identical TransformerEncoderBlocks in an
    MLN's layer list — the sub-stack MeshConfig.pipeline shards.
    Returns (lo, hi) or None."""
    import dataclasses
    from deeplearning4j_tpu.nn.conf.layers_transformer import (
        TransformerEncoderBlock)
    layers = getattr(model, "layers", None)
    if layers is None:
        return None
    best, i = None, 0
    while i < len(layers):
        if isinstance(layers[i], TransformerEncoderBlock):
            ref = dataclasses.asdict(layers[i])
            j = i
            while j < len(layers) and \
                    isinstance(layers[j], TransformerEncoderBlock) and \
                    dataclasses.asdict(layers[j]) == ref:
                j += 1
            if best is None or j - i > best[1] - best[0]:
                best = (i, j)
            i = j
        else:
            i += 1
    return best if best is not None and best[1] - best[0] >= 2 else None


class ShardedTrainer:
    """Drives a MultiLayerNetwork/ComputationGraph's solver step under a
    mesh.  ``fit_batch`` is the hot path; ``fit`` drives an iterator like
    ``ParallelWrapper.fit`` did.

    ``MeshConfig.pipeline > 1`` (MLN with a homogeneous
    TransformerEncoderBlock run) swaps the middle of the step for the
    GPipe schedule: the run's parameters restack onto a
    pipe-axis-sharded leading dim, ``gpipe_apply`` runs the schedule,
    and DP/TP compose on the remaining mesh axes (TP stays
    auto-partitioned by GSPMD inside the stage body).  The model's own
    params tree is refreshed (unstacked) after every ``fit``/
    ``fit_batch`` so ``output``/checkpointing keep working."""

    def __init__(self, model, mesh_conf: Optional[MeshConfig] = None,
                 devices=None, n_micro: int = 4):
        self.model = model
        self.mesh_conf = mesh_conf or MeshConfig.data_parallel()
        self.mesh = self.mesh_conf.build(devices)
        self.tp = self.mesh_conf.model
        self.n_micro = n_micro
        self._step_counter = _STEPS.labels(worker=jax.process_index())
        model._check_init()
        if self.mesh_conf.pipeline > 1:
            self._init_pipelined()
            return
        self._pipe = None
        model._build_solver()
        self.solver = model._solver

        # Build sharding trees and place params/opt/model state.
        shardable = _tp_shardable_layers(model)

        def sharding_tree(tree):
            return jax.tree_util.tree_map_with_path(
                lambda p, a: NamedSharding(
                    self.mesh, _param_spec(p, np.shape(a), self.tp,
                                           shardable)), tree)

        self._param_shardings = sharding_tree(model.params_tree)
        self._replicated = NamedSharding(self.mesh, P())
        model.params_tree = jax.device_put(model.params_tree,
                                           self._param_shardings)
        if model.opt_state is None:
            model.opt_state = self.solver.init_opt_state(model.params_tree)
        self._opt_shardings = sharding_tree(model.opt_state)
        model.opt_state = jax.device_put(model.opt_state, self._opt_shardings)
        model.state_tree = jax.device_put(
            model.state_tree,
            jax.tree_util.tree_map(lambda a: self._replicated,
                                   model.state_tree))
    # -- pipeline path (MeshConfig.pipeline > 1) -----------------------
    def _init_pipelined(self):
        import dataclasses
        from deeplearning4j_tpu.nn.conf.layers_core import BaseOutputLayerConf
        from deeplearning4j_tpu.parallel.pipeline import gpipe_apply

        model, S = self.model, self.mesh_conf.pipeline
        run = _find_block_run(model)
        if run is None:
            raise ValueError(
                "MeshConfig.pipeline > 1 needs a MultiLayerNetwork "
                "with a run of >= 2 conf-identical "
                "TransformerEncoderBlocks to shard into stages")
        lo, hi = run
        if (hi - lo) % S:
            raise ValueError(
                f"{hi - lo} pipelined blocks do not divide over "
                f"{S} stages")
        if getattr(model.conf, "frozen_layers", None):
            raise ValueError("pipeline path does not support frozen "
                             "layers yet")
        if model.conf.backprop_type != "standard":
            raise ValueError("pipeline path supports standard backprop "
                             "only (no tBPTT)")
        if not isinstance(model.layers[-1], BaseOutputLayerConf):
            raise ValueError("last layer must be an output layer")
        drop = getattr(model.layers[lo], "dropout", 0) or 0
        if drop:
            log.warning("pipelined blocks run without dropout "
                        "(configured rate %.3g)", drop)
        self._pipe = (lo, hi)
        _PIPE_BUBBLE.set((S - 1) / (S - 1 + self.n_micro))

        tp, mesh = self.tp, self.mesh
        tp_rules = {"Wqkv": "col", "W1": "col", "W2": "row", "Wo": "row"}

        def stacked_spec(path, a):
            key = getattr(path[-1], "key", str(path[-1]))
            kind = tp_rules.get(key)
            if tp > 1 and kind and np.ndim(a) == 3:
                if kind == "col" and a.shape[-1] % tp == 0:
                    return P("pipeline", None, "model")
                if kind == "row" and a.shape[1] % tp == 0:
                    return P("pipeline", "model", None)
            return P("pipeline")

        shardable = _tp_shardable_layers(model)

        def outer_spec(name):
            def f(path, a):
                keys = [getattr(p, "key", str(p)) for p in path]
                kind = shardable.get(name, {}).get(keys[-1])
                if tp > 1 and kind and np.ndim(a) == 2:
                    if kind == "col" and a.shape[-1] % tp == 0:
                        return P(None, "model")
                    if kind == "row" and a.shape[0] % tp == 0:
                        return P("model", None)
                return P()
            return f

        # copies, not views: the jitted step DONATES its params, and
        # donated aliases of the model's own tree would delete them
        cp = lambda t: jax.tree_util.tree_map(jnp.array, t)

        def place(tree, spec_fn):
            return jax.device_put(tree, jax.tree_util.tree_map_with_path(
                lambda p, a: NamedSharding(mesh, spec_fn(p, a)), tree))

        def stack_and_place():
            """model.params_tree (per-layer) -> placed pipe params
            {pre, blocks (stacked [S] leading axis), post} — used at
            init AND as the inverse of ``sync_model`` when a restored
            checkpoint overwrites the model tree (resume/rollback)."""
            blocks = [model.params_tree[f"layer_{i}"]
                      for i in range(lo, hi)]
            stacked = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *blocks)
            pre = {f"layer_{i}": cp(model.params_tree[f"layer_{i}"])
                   for i in range(lo)}
            post = {f"layer_{i}": cp(model.params_tree[f"layer_{i}"])
                    for i in range(hi, len(model.layers))}
            params = {"pre": pre, "blocks": place(stacked, stacked_spec),
                      "post": post}
            for part in ("pre", "post"):
                for name in params[part]:
                    params[part][name] = place(params[part][name],
                                               outer_spec(name))
            return params

        self._stack_and_place = stack_and_place
        self._updater = model._updater
        self._restack()

        layers, confs = model.layers, model.conf
        block_conf = layers[lo]
        out_layer = layers[-1]
        n_micro = self.n_micro
        d_axis = "data" if self.mesh_conf.data > 1 else None
        compute_dtype = model._compute_dtype
        state0 = {k: dict(v) for k, v in model.state_tree.items()}

        def apply_outer(p, i, x):
            prep = confs.preprocessors[i]
            if prep is not None:
                x = prep(x)
            y, _ = layers[i].apply(p[f"layer_{i}"],
                                   state0[f"layer_{i}"], x,
                                   training=False,
                                   compute_dtype=compute_dtype)
            return y

        def loss_fn(params, batch):
            x, labels = batch["features"], batch["labels"]
            for i in range(lo):
                x = apply_outer(params["pre"], i, x)
            x = gpipe_apply(
                mesh, params["blocks"], x,
                lambda p, a: block_conf.apply(
                    p, {}, a, training=False,
                    compute_dtype=compute_dtype)[0],
                n_micro, axis="pipeline", data_axis=d_axis)
            for i in range(hi, len(layers) - 1):
                x = apply_outer(params["post"], i, x)
            prep = confs.preprocessors[-1]
            if prep is not None:
                x = prep(x)
            last = f"layer_{len(layers) - 1}"
            z = out_layer.pre_output(params["post"][last], x,
                                     compute_dtype)
            scores = out_layer.per_example_score(
                labels, z, None, head_input=x,
                params=params["post"][last])
            return jnp.mean(scores) + self._pipe_reg(params)

        from deeplearning4j_tpu.optimize.solver import (
            apply_updates_if, finite_step_ok, select_step)

        def train_step(params, opt_state, it, batch, lr_scale):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            # same bad-step guard as Solver._step_impl (shared
            # helpers): a non-finite loss/grad step must not move
            # params or optimizer state, while the NaN loss still
            # reaches the host-side policy
            ok = finite_step_ok(loss, grads)
            old_opt_state = opt_state
            updates, opt_state = self._updater.update(
                grads, opt_state, params, it)
            params = apply_updates_if(ok, params, updates, lr_scale)
            opt_state = self._updater.finalize(opt_state, params)
            opt_state = select_step(ok, opt_state, old_opt_state)
            return params, opt_state, loss

        # module ``jit_train_step`` in a profile, as the Solver's
        self._pipe_step = jax.jit(train_step, donate_argnums=(0, 1))
        # ADVICE r5 perf: unstacking every pipelined block back into
        # the model tree after EVERY step is host-side overhead on the
        # hot path that grows with model size.  Sync lazily instead:
        # steps mark the model tree stale, and the unstack runs only
        # when something actually reads it — model.output()/score()/
        # serialization reach sync_model through this hook.  The hook
        # holds the trainer WEAKLY: a model outliving its trainer must
        # not pin the stacked pipe params + optimizer state in memory.
        import weakref
        wr = weakref.ref(self)

        def _hook():
            tr = wr()
            if tr is not None:
                tr.sync_model()

        def _discard_pending():
            # hook protocol: after an external restore overwrites the
            # model tree, drop any deferred unstack so it cannot
            # clobber the restored weights (parallel/checkpoint.py) —
            # and schedule the INVERSE: the next pipelined step must
            # restack the restored per-layer tree into the pipe-sharded
            # params/opt before it runs (fit(resume=True) / rollback)
            tr = wr()
            if tr is not None:
                tr._model_stale = False
                tr._restack_needed = True

        def _sync_opt():
            # checkpoint-capture protocol (parallel/checkpoint.py): the
            # pipeline optimizer state lives trainer-side in the
            # pipe-sharded structure; copy it into model.opt_state so
            # a checkpoint stores it (copies — the pipe step DONATES
            # the live buffers, and an async orbax save must not read
            # storage the next step reclaims)
            tr = wr()
            if tr is not None:
                tr.model.opt_state = jax.tree_util.tree_map(
                    jnp.array, tr._pipe_opt)
        _hook.discard_pending = _discard_pending
        _hook.sync_opt = _sync_opt
        model._param_sync_hook = _hook

    def _restack(self):
        """(Re)build the pipe-axis-sharded ``_pipe_params``/``_pipe_opt``
        from the model's per-layer trees — the inverse of
        ``sync_model``.  Runs at init and lazily before the next step
        after an external restore overwrote the model tree
        (``fit(resume=True)``, BadStepPolicy rollback): the restored
        optimizer state is adopted when it has the pipe structure
        (i.e. the checkpoint came from a pipeline run, captured via the
        hook's ``sync_opt``), re-placed onto the init-time shardings;
        anything else (fresh model, params-only restore) gets freshly
        initialized optimizer state."""
        params = self._stack_and_place()
        self._pipe_params = params
        fresh_opt = self._updater.init_state(params)
        restored = self.model.opt_state
        if restored is not None and \
                jax.tree_util.tree_structure(restored) != \
                jax.tree_util.tree_structure(fresh_opt):
            # elastic N→M resume: a checkpoint from a plain (or
            # differently-staged) trainer carries the per-layer
            # optimizer layout — restack it into this trainer's pipe
            # structure (byte-preserving per layer) instead of
            # discarding momentum
            from deeplearning4j_tpu.parallel import elastic
            converted = elastic.convert_opt_layout(restored, fresh_opt)
            if converted is not None:
                log.info("restacking restored per-layer optimizer "
                         "state into the %d-stage pipeline layout",
                         self.mesh_conf.pipeline)
                restored = converted
        if restored is not None and \
                jax.tree_util.tree_structure(restored) == \
                jax.tree_util.tree_structure(fresh_opt):
            self._pipe_opt = jax.tree_util.tree_map(
                lambda z, r: jax.device_put(jnp.asarray(r), z.sharding),
                fresh_opt, restored)
        else:
            self._pipe_opt = fresh_opt
        self._model_stale = False
        self._restack_needed = False

    def _pipe_reg(self, params):
        """l1/l2 over all layers from the TRACED params — a sum over a
        stacked-blocks leaf equals the per-layer sums it replaces, so
        the run is counted exactly once (at i == lo)."""
        model, reg = self.model, 0.0
        (lo, hi) = self._pipe
        from deeplearning4j_tpu.utils.trees import get_path
        for i, ly in enumerate(model.layers):
            l1 = ly.l1 or 0.0
            l2 = ly.l2 or 0.0
            if not (l1 or l2):
                continue
            if lo < i < hi:
                continue                 # run counted once, at i == lo
            for name in ly.regularized_param_names():
                if i == lo:
                    w = get_path(params["blocks"], name)
                else:
                    part = "pre" if i < lo else "post"
                    w = get_path(params[part][f"layer_{i}"], name)
                if w is None:
                    continue
                if l1:
                    reg = reg + l1 * jnp.sum(jnp.abs(w))
                if l2:
                    reg = reg + 0.5 * l2 * jnp.sum(jnp.square(w))
        return reg

    def sync_model(self):
        """Unstack the pipelined params back into the model's tree so
        ``output``/serialization see the trained weights.  Lazy: a
        no-op unless a pipelined step ran since the last sync (the
        model's ``_param_sync_hook`` calls this on demand, so the
        per-step hot path never pays the unstack)."""
        if self._pipe is None or not self._model_stale:
            return
        self._model_stale = False
        lo, hi = self._pipe
        m = self.model
        p = self._pipe_params
        # COPIES, not views: the next pipelined step donates the live
        # pre/post buffers, and the model tree (or an async checkpoint
        # save holding it) must not reference reclaimed storage
        for name, tree in {**p["pre"], **p["post"]}.items():
            m.params_tree[name] = jax.tree_util.tree_map(jnp.array, tree)
        for j in range(hi - lo):
            m.params_tree[f"layer_{lo + j}"] = jax.tree_util.tree_map(
                lambda a, _j=j: a[_j], p["blocks"])

    def _shard_batch(self, batch: dict) -> dict:
        """Place every batch leaf (arrays, possibly nested per-input dicts
        for multi-input graphs) batch-sharded over the 'data' axis.
        Multi-process contract (the fleet workers): every process feeds
        the IDENTICAL global batch; each assembles its own addressable
        shards locally (``make_array_from_callback``) — ``device_put``
        onto a cross-process sharding needs collective value checks the
        CPU backend cannot run, and the data plane should not pay a
        broadcast for bytes every host already holds."""
        multi = jax.process_count() > 1

        def place(v):
            parts = [None] * np.ndim(v)
            if self.mesh_conf.data > 1 and np.ndim(v) >= 1:
                if np.shape(v)[0] % self.mesh_conf.data:
                    # typed, not an XLA shape error: an elastic
                    # supervisor must distinguish "this world cannot
                    # carry the configured global batch" (pick another
                    # M, or pad the batch) from a training failure
                    from deeplearning4j_tpu.resilience.errors import (
                        ElasticWorldError)
                    raise ElasticWorldError(
                        f"global batch of {np.shape(v)[0]} examples "
                        f"does not divide over data={self.mesh_conf.data}"
                        " — a shrunk/grown fleet keeps the GLOBAL batch "
                        "size by resizing per-rank microbatches, which "
                        "only works in whole examples")
                parts[0] = "data"
            sharding = NamedSharding(self.mesh, P(*parts))
            if multi:
                host = np.asarray(v)
                return jax.make_array_from_callback(
                    host.shape, sharding, lambda idx: host[idx])
            return jax.device_put(jnp.asarray(v), sharding)
        return jax.tree_util.tree_map(place, batch)

    def _step_dict(self, batch: dict):
        """Run the compiled sharded step on a prepared batch dict WITHOUT
        touching the model's iteration counters (telemetry step counters
        DO advance — they count dispatches, not fit-loop iterations)."""
        m = self.model
        tracer = telemetry.get_tracer()
        if self._pipe is not None:
            if self._restack_needed:
                # a restore overwrote the model tree since the last
                # step (resume / rollback): rebuild the pipe-sharded
                # params/opt from it before stepping
                self._restack()
            if "features_mask" in batch or "labels_mask" in batch:
                raise ValueError("pipeline path does not support "
                                 "masked batches yet")
            batch = self._shard_batch(
                {"features": batch["features"],
                 "labels": batch["labels"]})
            # device-phase sample (ISSUE 13): 1-in-N steps pays a
            # block_until_ready on the loss so the fleet scrape gains
            # per-device optimizer-step time; the other steps keep the
            # async dispatch-ahead pipeline intact
            prof = telemetry.get_profiler()
            with prof.measure("optimizer_step",
                              every=_PROFILE_STEP_EVERY) as pm:
                with tracer.span("train/pipeline_step",
                                 mesh=str(dict(self.mesh.shape))), \
                        self.mesh:
                    (self._pipe_params, self._pipe_opt, loss) = \
                        self._pipe_step(
                            self._pipe_params, self._pipe_opt,
                            m.iteration_count, batch,
                            float(getattr(m, "_lr_backoff", 1.0)))
                pm.ready(loss)
            self._model_stale = True
            self._step_counter.inc()   # dispatched, not failed validation
            return loss
        batch = self._shard_batch(batch)
        prof = telemetry.get_profiler()
        with prof.measure("optimizer_step",
                          every=_PROFILE_STEP_EVERY) as pm:
            # the attention router learns the mesh at TRACE time: a
            # Mosaic kernel cannot be partitioned by GSPMD and has to
            # map itself over the batch/head axes
            with tracer.span("train/sharded_step",
                             mesh=str(dict(self.mesh.shape))), \
                    self.mesh, trace_mesh(self.mesh):
                (m.params_tree, m.opt_state, m.state_tree, loss) = \
                    self.solver.step(
                        m.params_tree, m.opt_state, m.state_tree,
                        m.iteration_count, batch, m._rng.next_key(),
                        lr_scale=getattr(m, "_lr_backoff", 1.0))
            pm.ready(loss)
        self._step_counter.inc()
        return loss

    def _step_batch(self, features, labels, features_mask=None,
                    labels_mask=None):
        batch = {"features": features, "labels": labels}
        if features_mask is not None:
            batch["features_mask"] = features_mask
        if labels_mask is not None:
            batch["labels_mask"] = labels_mask
        return self._step_dict(batch)

    def fit_batch(self, features, labels, features_mask=None,
                  labels_mask=None):
        """One global step: shard inputs, run the compiled step, return
        loss.  Equivalent to one synchronized ParallelWrapper averaging
        round — except synchronization is an XLA all-reduce over ICI.
        On the pipeline path the model's own tree syncs LAZILY (the
        unstack runs when ``output``/serialization next reads it, not
        per step)."""
        loss = self._step_batch(features, labels, features_mask, labels_mask)
        self.model.iteration_count += 1
        return loss

    def fit(self, iterator, n_epochs: int = 1, resume: bool = False):
        """Drive an iterator through the sharded step — the same shared
        epoch loop as MultiLayerNetwork/ComputationGraph.fit, so tBPTT,
        MultiDataSet batches, listener ordering and counters agree.

        ``resume=True`` restores the newest checkpoint from the
        attached ``CheckpointListener`` before training (run_fit
        semantics: ``n_epochs`` is then the TOTAL target) — the
        preemption-recovery entry for sharded training.  On the
        pipeline path the restored per-layer tree (and the pipe-
        structured optimizer state the checkpoint captured via the
        hook's ``sync_opt``) is restacked into the pipe-axis-sharded
        ``_pipe_params``/``_pipe_opt`` before the first step — the
        inverse of ``sync_model`` — preserving step/epoch/rng counters,
        so pipeline kill-and-resume is bit-identical like the MLN
        path."""
        out = run_fit(self.model, iterator, n_epochs, self._step_dict,
                      resume=resume)
        self.sync_model()
        return out
