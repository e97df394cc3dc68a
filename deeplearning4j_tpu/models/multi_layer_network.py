"""MultiLayerNetwork: the sequential-stack model.

Parity with ``org.deeplearning4j.nn.multilayer.MultiLayerNetwork`` (~4 kLoC
upstream): ``init/fit/output/feedForward/score/evaluate``, listener bus,
epoch/iteration counters, flattened-params view, clone, summary.

TPU-first execution model: ``fit`` drives ONE jitted step per minibatch —
forward + loss + jax.grad backward + updater fused by XLA, with parameter
and optimizer-state buffers donated (updated in place in HBM).  This
replaces DL4J's per-op eager path (Solver → computeGradientAndScore →
thousands of JNI crossings) and its cuDNN helper seam entirely.
"""
from __future__ import annotations

import logging
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterator import (
    AsyncDataSetIterator, DataSetIterator, ListDataSetIterator)
from deeplearning4j_tpu.eval.classification import Evaluation
from deeplearning4j_tpu.eval.regression import RegressionEvaluation
from deeplearning4j_tpu.eval.roc import ROCMultiClass
from deeplearning4j_tpu.nn.conf.base import BaseLayerConf
from deeplearning4j_tpu.nn.conf.builder import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf.layers_core import BaseOutputLayerConf
from deeplearning4j_tpu.optimize.listeners import TrainingListener
from deeplearning4j_tpu.optimize.solver import Solver
from deeplearning4j_tpu.optimize.fit_loop import run_fit
from deeplearning4j_tpu.optimize.updaters import updater_from_dict
from deeplearning4j_tpu.runtime.backend import backend
from deeplearning4j_tpu.runtime.dtype import canonical_dtype
from deeplearning4j_tpu.runtime.rng import RngKeyManager

log = logging.getLogger("deeplearning4j_tpu")


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers: Sequence[BaseLayerConf] = conf.layers
        self.params_tree = None
        self.state_tree = None
        self.opt_state = None
        self.listeners: List[TrainingListener] = []
        self.iteration_count = 0
        self.epoch_count = 0
        self.last_batch_size = 0
        self._rng = RngKeyManager(conf.global_conf.seed)
        self._dtype = canonical_dtype(conf.global_conf.dtype)
        cd = getattr(conf.global_conf, "compute_dtype", None)
        self._compute_dtype = (canonical_dtype(cd) if cd
                               else backend().compute_dtype)
        self._updater = updater_from_dict(conf.global_conf.updater)
        self._solver: Optional[Solver] = None
        self._output_fn = jax.jit(self._forward_infer)
        self._score_fn = jax.jit(self._score_batch_infer)

    # ------------------------------------------------------------------
    # Initialization
    # ------------------------------------------------------------------
    def init(self, seed: Optional[int] = None) -> "MultiLayerNetwork":
        """Initialize parameters (DL4J ``MultiLayerNetwork.init()``)."""
        if seed is not None:
            self._rng.reset(seed)
        params, states = {}, {}
        keys = self._rng.next_keys(len(self.layers))
        for i, (ly, key) in enumerate(zip(self.layers, keys)):
            p, s = ly.init(key, self._dtype)
            params[f"layer_{i}"] = p
            states[f"layer_{i}"] = s
        self.params_tree = params
        self.state_tree = states
        self.opt_state = None  # lazily built at first fit
        return self

    def _check_init(self):
        if self.params_tree is None:
            self.init()
        # a trainer holding the authoritative (e.g. pipeline-stacked)
        # params installs this hook; it refreshes params_tree lazily so
        # the per-step hot path never pays the sync (ADVICE r5 perf)
        hook = self.__dict__.get("_param_sync_hook")
        if hook is not None:
            hook()

    # ------------------------------------------------------------------
    # Pure forward/score (traced by XLA)
    # ------------------------------------------------------------------
    def _forward_layers(self, params, state, x, training, rng, upto=None,
                        mask=None):
        """Run layers [0, upto); returns (activation, new_state_tree).
        `mask` is the features mask ([b, t] for sequences) handed to
        mask-aware layers (``USES_MASK``) — DL4J's setMaskArray propagation.
        """
        compute_dtype = self._compute_dtype
        n = len(self.layers) if upto is None else upto
        keys = (jax.random.split(rng, n) if rng is not None
                else [None] * n)
        new_state = dict(state)
        for i in range(n):
            ly = self.layers[i]
            pre = self.conf.preprocessors[i]
            if pre is not None:
                x = pre(x)
            kwargs = {}
            if getattr(ly, "USES_MASK", False):
                kwargs["mask"] = mask
            if getattr(ly, "tied_to", None) is not None:
                # a head that reads another layer's table (TiedLMHead)
                kwargs["tied"] = params[f"layer_{ly.tied_to}"]
            x, s = ly.apply(
                params[f"layer_{i}"], state[f"layer_{i}"], x,
                training=training, rng=keys[i], compute_dtype=compute_dtype,
                **kwargs)
            new_state[f"layer_{i}"] = s
        return x, new_state

    def _forward_infer(self, params, state, x, mask=None):
        y, _ = self._forward_layers(params, state, x, False, None, mask=mask)
        return y

    def _regularization_score(self, params):
        from deeplearning4j_tpu.utils.trees import get_path
        reg = 0.0
        for i, ly in enumerate(self.layers):
            l1 = ly.l1 or 0.0
            l2 = ly.l2 or 0.0
            if not (l1 or l2):
                continue
            for name in ly.regularized_param_names():
                w = get_path(params[f"layer_{i}"], name)
                if w is None:
                    continue
                if l1:
                    reg = reg + l1 * jnp.sum(jnp.abs(w))
                if l2:
                    # DL4J L2Regularization score: 0.5 * l2 * sum(w^2)
                    reg = reg + 0.5 * l2 * jnp.sum(jnp.square(w))
        return reg

    def _score_batch(self, params, state, batch, rng, training):
        """Mean per-example loss + regularization (DL4J ``score()``)."""
        x = batch["features"]
        labels = batch["labels"]
        lmask = batch.get("labels_mask")
        fmask = batch.get("features_mask")
        out_layer = self.layers[-1]
        if not isinstance(out_layer, BaseOutputLayerConf):
            raise ValueError("Last layer must be an output/loss layer for fit()")
        h, new_state = self._forward_layers(
            params, state, x, training, rng, upto=len(self.layers) - 1,
            mask=fmask)
        pre = self.conf.preprocessors[-1]
        if pre is not None:
            h = pre(h)
        z = out_layer.pre_output(
            params[f"layer_{len(self.layers) - 1}"], h,
            self._compute_dtype)
        # Distinct key for head sampling (e.g. VAE reparameterization):
        # `rng` itself already parented the per-layer dropout splits.
        head_rng = None if rng is None else jax.random.fold_in(rng, 0x5eed)
        scores = out_layer.per_example_score(
            labels, z, lmask, head_input=h, rng=head_rng,
            params=params[f"layer_{len(self.layers) - 1}"])
        if lmask is not None:
            denom = jnp.maximum(jnp.sum(lmask), 1.0)
            loss = jnp.sum(scores) / denom
        else:
            loss = jnp.mean(scores)
        return loss + self._regularization_score(params), new_state

    def _score_batch_infer(self, params, state, batch):
        loss, _ = self._score_batch(params, state, batch, None, False)
        return loss

    # ------------------------------------------------------------------
    # Training
    # ------------------------------------------------------------------
    def _trainable_mask(self):
        """0/1 mask pytree from conf.frozen_layers (persisted through
        save/load — the ONLY freezing mechanism, so it always
        survives serialization)."""
        frozen = set(getattr(self.conf, "frozen_layers", ()) or ())
        if not frozen:
            return None
        return {f"layer_{i}": jax.tree_util.tree_map(
                    lambda _: 0.0 if i in frozen else 1.0,
                    self.params_tree[f"layer_{i}"])
                for i in range(len(self.layers))}

    def _build_solver(self):
        if self._solver is not None:
            return
        from deeplearning4j_tpu.utils.trees import get_path, set_path
        decay_tree = jax.tree_util.tree_map(lambda _: 0.0, self.params_tree)
        any_decay = False
        for i, ly in enumerate(self.layers):
            wd = ly.weight_decay or 0.0
            if wd:
                any_decay = True
                for name in ly.regularized_param_names():
                    if get_path(decay_tree[f"layer_{i}"], name) is not None:
                        set_path(decay_tree[f"layer_{i}"], name, wd)
        self._solver = Solver(
            score_fn=self._score_batch,
            updater=self._updater,
            grad_normalization=self.conf.grad_normalization,
            grad_norm_threshold=self.conf.grad_norm_threshold,
            minimize=self.conf.global_conf.minimize,
            decay_tree=decay_tree if any_decay else None,
            trainable_tree=self._trainable_mask(),
        )
        if self.opt_state is None:
            self.opt_state = self._solver.init_opt_state(self.params_tree)

    @staticmethod
    def _batch_dict(ds: DataSet):
        b = {"features": jnp.asarray(ds.features),
             "labels": jnp.asarray(ds.labels)}
        if ds.labels_mask is not None:
            b["labels_mask"] = jnp.asarray(ds.labels_mask)
        if ds.features_mask is not None:
            b["features_mask"] = jnp.asarray(ds.features_mask)
        return b

    def fit(self, data: Union[DataSet, DataSetIterator], n_epochs: int = 1,
            async_prefetch: bool = True, resume: bool = False):
        """Train (DL4J ``fit(DataSetIterator, numEpochs)`` /
        ``fit(DataSet)``).  Wraps the iterator in async prefetch exactly as
        DL4J wraps in ``AsyncDataSetIterator``.  ``resume=True`` restores
        the newest checkpoint from an attached ``CheckpointListener``
        first (``n_epochs`` is then the TOTAL epoch target)."""
        self._check_init()
        self._build_solver()
        if isinstance(data, DataSet):
            # fit(DataSet) bypasses async prefetch (nothing to overlap),
            # like DL4J's fit(DataSet) vs fit(DataSetIterator).
            iterator: DataSetIterator = ListDataSetIterator([data])
            async_prefetch = False
        else:
            iterator = data
        wrapped = (AsyncDataSetIterator(iterator)
                   if async_prefetch and not isinstance(
                       iterator, AsyncDataSetIterator)
                   else iterator)

        return run_fit(self, wrapped, n_epochs, reset_target=iterator,
                       resume=resume)

    # ------------------------------------------------------------------
    # Recurrent state management (DL4J rnnTimeStep / tBPTT semantics)
    # ------------------------------------------------------------------
    def _has_rnn(self) -> bool:
        return any(getattr(ly, "IS_RNN", False) for ly in self.layers)

    @staticmethod
    def _tbptt_chunks(ds: DataSet, length: int):
        """Split a sequence DataSet along time into tBPTT segments
        (DL4J ``MultiLayerNetwork.doTruncatedBPTT``)."""
        from deeplearning4j_tpu.data.dataset import tbptt_segments
        return tbptt_segments(ds, length)

    def rnn_clear_previous_state(self):
        """Drop stored recurrent carries (DL4J ``rnnClearPreviousState``)."""
        from deeplearning4j_tpu.nn.conf.layers_recurrent import strip_rnn_carry
        self._rnn_state_map = None
        if self.state_tree is not None:
            self.state_tree = strip_rnn_carry(self.state_tree)

    def rnn_time_step(self, x, features_mask=None):
        """Streaming inference: run these timesteps continuing from the
        stored recurrent state, store the new state (DL4J ``rnnTimeStep``).
        ``x``: [b, t, f] (or [b, f] for a single step -> returns [b, out]).

        Like DL4J's ``stateMap``, the streaming carry lives in a SEPARATE
        map (not the model's state tree), so interleaved ``output``/
        ``score`` calls still start from zero state."""
        self._check_init()
        x = jnp.asarray(x)
        single = x.ndim == 2
        if single:
            x = x[:, None, :]
        if features_mask is not None:
            features_mask = jnp.asarray(features_mask)
        carry = getattr(self, "_rnn_state_map", None)
        state_in = dict(self.state_tree)
        if carry is not None:
            for lname, lcarry in carry.items():
                state_in[lname] = {**state_in[lname], **lcarry}
        y, new_state = self._rnn_step_jit(
            self.params_tree, state_in, x, features_mask)
        self._rnn_state_map = {
            lname: {k: v for k, v in lstate.items()
                    if k.startswith("rnn_")}
            for lname, lstate in new_state.items()}
        return y[:, -1] if single else y

    def _rnn_step_impl(self, params, state, x, mask):
        y, new_state = self._forward_layers(params, state, x, False, None,
                                            mask=mask)
        return y, new_state

    @property
    def _rnn_step_jit(self):
        if not hasattr(self, "_rnn_step_fn"):
            self._rnn_step_fn = jax.jit(self._rnn_step_impl)
        return self._rnn_step_fn

    # ------------------------------------------------------------------
    # Inference / scoring
    # ------------------------------------------------------------------
    def output(self, x, training: bool = False, features_mask=None):
        """Forward pass returning final-layer activations
        (DL4J ``output(INDArray[, featuresMask])``)."""
        self._check_init()
        x = jnp.asarray(x)
        if features_mask is not None:
            features_mask = jnp.asarray(features_mask)
        if training:
            y, _ = self._forward_layers(self.params_tree, self.state_tree, x,
                                        True, self._rng.next_key(),
                                        mask=features_mask)
            return y
        return self._output_fn(self.params_tree, self.state_tree, x,
                               features_mask)

    def feed_forward(self, x, training: bool = False) -> List[jnp.ndarray]:
        """All per-layer activations (DL4J ``feedForward``)."""
        self._check_init()
        x = jnp.asarray(x)
        acts = [x]
        compute_dtype = self._compute_dtype
        rng = self._rng.next_key() if training else None
        keys = (jax.random.split(rng, len(self.layers)) if rng is not None
                else [None] * len(self.layers))
        state = self.state_tree
        for i, ly in enumerate(self.layers):
            pre = self.conf.preprocessors[i]
            if pre is not None:
                x = pre(x)
            x, _ = ly.apply(self.params_tree[f"layer_{i}"],
                            state[f"layer_{i}"], x, training=training,
                            rng=keys[i], compute_dtype=compute_dtype)
            acts.append(x)
        return acts

    def score(self, ds: DataSet) -> float:
        """Loss on a dataset without updating (DL4J ``score(DataSet)``)."""
        self._check_init()
        return float(self._score_fn(self.params_tree, self.state_tree,
                                    self._batch_dict(ds)))

    def evaluate(self, iterator: DataSetIterator, top_n: int = 1) -> Evaluation:
        """(DL4J ``evaluate(DataSetIterator)``)."""
        self._check_init()
        ev = Evaluation(top_n=top_n)
        for ds in iterator:
            out = self.output(ds.features, features_mask=ds.features_mask)
            ev.eval(ds.labels, np.asarray(out), ds.labels_mask)
        iterator.reset()
        return ev

    def evaluate_regression(self, iterator) -> RegressionEvaluation:
        self._check_init()
        ev = RegressionEvaluation()
        for ds in iterator:
            ev.eval(ds.labels, np.asarray(self.output(ds.features)),
                    ds.labels_mask)
        iterator.reset()
        return ev

    def evaluate_roc(self, iterator, exact: bool = True) -> ROCMultiClass:
        self._check_init()
        roc = ROCMultiClass(exact=exact)
        for ds in iterator:
            roc.eval(ds.labels, np.asarray(self.output(ds.features)),
                     ds.labels_mask)
        iterator.reset()
        return roc

    # ------------------------------------------------------------------
    # Parameter access (DL4J flattened-vector parity views)
    # ------------------------------------------------------------------
    def _leaf_order(self):
        """((path...), leaf) pairs, layer-major then name-sorted (nested
        dicts — e.g. Bidirectional's {fwd, bwd} — walked depth-first)."""
        from deeplearning4j_tpu.utils.trees import iter_leaves
        for i in range(len(self.layers)):
            for path, leaf in iter_leaves(self.params_tree[f"layer_{i}"]):
                yield (f"layer_{i}",) + path, leaf

    def params(self) -> np.ndarray:
        """One flattened host vector, layer-major then name-sorted — the
        DL4J ``params()`` view (order: per layer W then b)."""
        self._check_init()
        parts = [np.asarray(leaf).reshape(-1)
                 for _, leaf in self._leaf_order()]
        return (np.concatenate(parts) if parts
                else np.zeros((0,), np.float32))

    def set_params(self, vector: np.ndarray):
        from deeplearning4j_tpu.utils.trees import deep_copy_dicts, set_path
        self._check_init()
        vector = np.asarray(vector)
        off = 0
        new = deep_copy_dicts(self.params_tree)
        for path, arr in self._leaf_order():
            size = int(np.prod(arr.shape)) if arr.shape else 1
            set_path(new, path, jnp.asarray(
                vector[off:off + size].reshape(arr.shape), arr.dtype))
            off += size
        if off != vector.size:
            raise ValueError(f"Expected {off} values, got {vector.size}")
        self.params_tree = new

    def num_params(self) -> int:
        self._check_init()
        return sum(int(np.prod(np.asarray(l).shape))
                   for l in jax.tree_util.tree_leaves(self.params_tree))

    # ------------------------------------------------------------------
    # Misc parity API
    # ------------------------------------------------------------------
    def set_listeners(self, *listeners: TrainingListener):
        self.listeners = list(listeners)

    def add_listeners(self, *listeners: TrainingListener):
        self.listeners.extend(listeners)

    def clone(self) -> "MultiLayerNetwork":
        import copy
        hook = self.__dict__.get("_param_sync_hook")
        if hook is not None:
            hook()
        m = MultiLayerNetwork(MultiLayerConfiguration.from_dict(
            self.conf.to_dict()))
        if self.params_tree is not None:
            m.params_tree = jax.tree_util.tree_map(lambda a: a,
                                                   self.params_tree)
            m.state_tree = copy.deepcopy(
                jax.tree_util.tree_map(lambda a: a, self.state_tree))
        m.iteration_count = self.iteration_count
        m.epoch_count = self.epoch_count
        return m

    def summary(self) -> str:
        """Layer table (DL4J ``summary()``)."""
        self._check_init()
        from deeplearning4j_tpu.utils.trees import iter_leaves
        rows = [f"{'idx':<4} {'name':<22} {'type':<24} {'#params':>10}"]
        total = 0
        for i, ly in enumerate(self.layers):
            lp = self.params_tree[f"layer_{i}"]
            n = sum(int(np.prod(np.asarray(a).shape))
                    for _, a in iter_leaves(lp))
            total += n
            rows.append(f"{i:<4} {(ly.name or f'layer_{i}'):<22} "
                        f"{type(ly).__name__:<24} {n:>10}")
        rows.append(f"Total params: {total}")
        return "\n".join(rows)

    def save(self, path, save_updater: bool = True):
        from deeplearning4j_tpu.utils.model_serializer import write_model
        write_model(self, path, save_updater=save_updater)

    @staticmethod
    def load(path, load_updater: bool = True) -> "MultiLayerNetwork":
        from deeplearning4j_tpu.utils.model_serializer import (
            restore_multi_layer_network)
        return restore_multi_layer_network(path, load_updater=load_updater)
