"""Solver: assembles the single compiled train step.

Replaces DL4J's ``Solver`` → ``StochasticGradientDescent`` →
``BaseOptimizer`` chain (reference: ``org.deeplearning4j.optimize.solvers.
{Solver,StochasticGradientDescent,BaseOptimizer}``).  Where DL4J runs
``computeGradientAndScore`` (thousands of eager ops, one JNI crossing each)
then applies the updater in-place, here the WHOLE iteration — forward, loss,
backward (jax.grad), gradient normalization, updater math, parameter
update — is one XLA program.  Parameter and optimizer-state buffers are
donated, so the update is in-place in HBM (the workspace behavior DL4J got
from flattened-vector views).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.analysis import sanitize as _sanitize
from deeplearning4j_tpu.optimize.updaters import BaseUpdater


def check_numerics_enabled() -> bool:
    """NaN/Inf debug mode (``OpProfiler`` ``checkForNAN``/``checkForINF``
    analogue): ``DL4J_TPU_CHECK_NUMERICS=1`` makes every train step
    validate its loss and updated params host-side, naming the offending
    leaves.  Costs one device sync per step — a debug mode, as upstream."""
    import os
    return os.environ.get("DL4J_TPU_CHECK_NUMERICS", "") in ("1", "true")


def check_numerics(loss, params, step_idx: int):
    import numpy as np
    l = np.asarray(jax.device_get(loss))
    bad = []
    if not np.isfinite(l).all():
        bad.append(f"loss={float(l)}")
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            jax.device_get(params)):
        a = np.asarray(leaf)
        if not np.isfinite(a).all():
            name = "/".join(str(getattr(k, "key", k)) for k in path)
            n_bad = int((~np.isfinite(a)).sum())
            bad.append(f"params[{name}]: {n_bad}/{a.size} non-finite")
    if bad:
        raise FloatingPointError(
            f"Non-finite values after train step {step_idx} "
            f"(DL4J_TPU_CHECK_NUMERICS): " + "; ".join(bad[:8]))


def finite_step_ok(loss, grads, trainable_tree=None):
    """Scalar bool tracer: True iff the loss and every (trainable)
    gradient leaf are finite.  Exact per-leaf ``isfinite`` — a sum
    probe can overflow on large finite trees and false-positive;
    FROZEN leaves (``trainable_tree`` mask 0) are excluded — their
    grads are zeroed downstream and must not veto the step."""
    ok = jnp.isfinite(loss)
    mask_leaves = (jax.tree_util.tree_leaves(trainable_tree)
                   if trainable_tree is not None else None)
    for i, g in enumerate(jax.tree_util.tree_leaves(grads)):
        if mask_leaves is not None:
            g = jnp.where(mask_leaves[i] > 0, g, jnp.zeros_like(g))
        ok = ok & jnp.isfinite(g).all()
    return ok


def apply_updates_if(ok, params, updates, lr_scale):
    """``params - updates * lr_scale`` where ``ok``, else the old
    params.  ``lr_scale`` is the bad-step policy's backoff multiplier
    (cast per-leaf: bf16 updates stay bf16); ``jnp.where`` — not a
    multiply — skips the bad step, since ``0 * NaN`` would smear NaN
    into the params."""
    return jax.tree_util.tree_map(
        lambda p, u: jnp.where(ok, p - (u * lr_scale).astype(u.dtype),
                               p), params, updates)


def select_step(ok, new_tree, old_tree):
    """Per-leaf select between the post-step and pre-step tree (same
    structure required) — how optimizer/model state sits out a
    non-finite step."""
    return jax.tree_util.tree_map(
        lambda new, old: jnp.where(ok, new, old), new_tree, old_tree)


def normalize_gradients(grads, kind: Optional[str], threshold: float):
    """DL4J ``GradientNormalization`` semantics
    (``org.deeplearning4j.nn.conf.GradientNormalization``)."""
    if not kind or kind == "none":
        return grads
    if kind == "clip_element_wise_absolute_value":
        return jax.tree_util.tree_map(
            lambda g: jnp.clip(g, -threshold, threshold), grads)
    if kind == "clip_l2_per_layer":
        def clip(g):
            n = jnp.linalg.norm(g.reshape(-1))
            return g * jnp.minimum(1.0, threshold / (n + 1e-12))
        return jax.tree_util.tree_map(clip, grads)
    if kind == "renormalize_l2_per_layer":
        return jax.tree_util.tree_map(
            lambda g: g / (jnp.linalg.norm(g.reshape(-1)) + 1e-12), grads)
    if kind == "clip_l2_per_param_type":
        # DL4J ClipL2PerParamType: one clip per parameter TYPE (all the
        # W's together, all the b's together, ...) across layers.
        leaves_with_path = jax.tree_util.tree_leaves_with_path(grads)
        norms = {}
        for path, leaf in leaves_with_path:
            ptype = str(path[-1])
            norms[ptype] = norms.get(ptype, 0.0) + jnp.sum(jnp.square(leaf))

        def clip_by_type(path, g):
            n = jnp.sqrt(norms[str(path[-1])])
            return g * jnp.minimum(1.0, threshold / (n + 1e-12))

        return jax.tree_util.tree_map_with_path(clip_by_type, grads)
    if kind == "clip_global_norm":
        leaves = jax.tree_util.tree_leaves(grads)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
        scale = jnp.minimum(1.0, threshold / (gn + 1e-12))
        return jax.tree_util.tree_map(lambda g: g * scale, grads)
    raise ValueError(f"Unknown gradient normalization {kind!r}")


class Solver:
    """Owns the compiled step for one model.

    `score_fn(params, model_state, batch, rng, training) ->
    (loss, new_model_state)` is supplied by the network class; `batch` is a
    dict with 'features', 'labels', optional masks.
    """

    def __init__(
        self,
        score_fn: Callable,
        updater: BaseUpdater,
        grad_normalization: Optional[str] = None,
        grad_norm_threshold: float = 1.0,
        minimize: bool = True,
        decay_tree=None,
        trainable_tree=None,
    ):
        self.score_fn = score_fn
        self.updater = updater
        self.grad_normalization = grad_normalization
        self.grad_norm_threshold = grad_norm_threshold
        self.minimize = minimize
        # decay_tree: pytree of per-leaf weight-decay coefficients matching
        # the params structure (0.0 = no decay).  Applied DECOUPLED
        # (update += lr*wd*param), matching DL4J's WeightDecay
        # regularization (applyLR=true default), distinct from l2 which
        # contributes to the loss.
        self.decay_tree = decay_tree
        # trainable_tree: pytree of 1.0/0.0 masks matching params —
        # 0.0 leaves are FROZEN (DL4J FrozenLayer/TransferLearning's
        # setFeatureExtractor): their update is zeroed after decay, so
        # the parameter value never moves.
        self.trainable_tree = trainable_tree

        # the jitted callable's __name__ is the program's name in a
        # profile (module ``jit_train_step``): the benchmark's readers
        # and an operator reading xprof find the step by it
        def train_step(*args):
            return self._step_impl(*args)

        self._step = jax.jit(train_step, donate_argnums=(0, 1, 2))

    def init_opt_state(self, params):
        return self.updater.init_state(params)

    def _step_impl(self, params, opt_state, model_state, step_idx, batch,
                   rng, lr_scale):
        def loss_of(p):
            loss, new_state = self.score_fn(p, model_state, batch, rng, True)
            return (loss if self.minimize else -loss), new_state

        # value_and_grad, taken apart so that the two passes carry
        # their own scope in the profile's op names
        with jax.named_scope("forward"):
            loss, pullback, new_model_state = jax.vjp(
                loss_of, params, has_aux=True)
        with jax.named_scope("backward"):
            grads, = pullback(jnp.ones_like(loss))
        if not self.minimize:
            loss = -loss  # report the true (maximized) score, not -score
        # Bad-step guard (resilience layer): a non-finite loss or any
        # non-finite gradient must not move params / optimizer state /
        # model state — the loss is still RETURNED non-finite so the
        # host-side BadStepPolicy sees it and applies LR backoff or
        # rollback.  The reduction costs nothing next to the backward.
        ok = finite_step_ok(loss, grads, self.trainable_tree)
        if self.trainable_tree is not None:
            # zero frozen grads BEFORE normalization and the updater:
            # they must not inflate clip_global_norm or accumulate
            # momentum/Adam state (DL4J FrozenLayer contributes no
            # gradients at all)
            grads = jax.tree_util.tree_map(
                lambda g, m: g * m, grads, self.trainable_tree)
        grads = normalize_gradients(
            grads, self.grad_normalization, self.grad_norm_threshold)
        old_opt_state = opt_state
        with jax.named_scope("optimizer"):
            updates, opt_state = self.updater.update(
                grads, opt_state, params, step_idx)
            if self.decay_tree is not None:
                lr = self.updater.lr_at(step_idx)
                updates = jax.tree_util.tree_map(
                    lambda u, p, wd: u + lr * wd * p, updates, params,
                    self.decay_tree)
            if self.trainable_tree is not None:
                # updates masked too: weight decay and bias-correction
                # terms must not move frozen leaves either
                updates = jax.tree_util.tree_map(
                    lambda u, m: u * m, updates, self.trainable_tree)
            params = apply_updates_if(ok, params, updates, lr_scale)
            opt_state = self.updater.finalize(opt_state, params)
            opt_state = select_step(ok, opt_state, old_opt_state)
        # model state (batchnorm stats, rnn carry) keeps its old value
        # on a bad step too — but only when the structures line up: an
        # RNN's first chunk GROWS the state tree (empty -> carry), and
        # that structural change must go through regardless (the carry
        # of a skipped step is cleared at the next batch boundary).
        if jax.tree_util.tree_structure(new_model_state) == \
                jax.tree_util.tree_structure(model_state):
            new_model_state = select_step(ok, new_model_state,
                                          model_state)
        return params, opt_state, new_model_state, loss

    def step(self, params, opt_state, model_state, step_idx, batch, rng,
             lr_scale: float = 1.0):
        """One optimization iteration; returns (params, opt_state,
        model_state, loss).  Donated inputs must not be reused by caller.
        ``lr_scale`` multiplies the final update (BadStepPolicy backoff);
        passed traced, so changing it does not recompile."""
        # use-after-donate ledger (DL4J_TPU_SANITIZE=donation): the
        # step donates all three trees — a caller that re-reads an old
        # tree instead of the returned one trips here, not as silent
        # garbage.  Off: one frozenset lookup.  Ledger-marked BEFORE
        # the dispatch (a host-side weakref record, not a buffer read
        # — JIT105): a failed dispatch may have consumed the donated
        # buffers anyway, so the conservative marking stands.
        _sanitize.check_not_donated("solver/step", params, opt_state,
                                    model_state)
        _sanitize.mark_donated("solver/step", params, opt_state,
                               model_state)
        out = self._step(params, opt_state, model_state,
                         jnp.asarray(step_idx, jnp.int32), batch, rng,
                         float(lr_scale))
        if check_numerics_enabled():
            check_numerics(out[3], out[0], int(step_idx))
        return out
