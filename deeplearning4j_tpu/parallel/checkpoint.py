"""Sharded, preemption-safe checkpointing (orbax-backed).

The multi-host complement to ``utils.model_serializer`` (which writes one
host-side zip): saves the FULL training state — params, optimizer state,
model state, step/epoch counters — with each process writing its own
shards, async so the train loop isn't blocked, keep-K rotation like DL4J's
``CheckpointListener`` (reference:
``org.deeplearning4j.optimize.listeners.CheckpointListener`` keepLast/
logSaving; SURVEY.md §5.3-5.4 'checkpoint-restart driven' elasticity).
"""
from __future__ import annotations

import json
import logging
import os
from pathlib import Path
from typing import Any, Optional

try:
    import orbax.checkpoint as ocp
    _ORBAX_IMPORT_ERROR = None
except Exception as _e:  # degrade at import, fail loudly on first USE:
    ocp = None           # `from parallel import ...` must keep working
    _ORBAX_IMPORT_ERROR = _e   # on images without orbax baked in

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.optimize.listeners import TrainingListener
from deeplearning4j_tpu.parallel import elastic as _elastic
from deeplearning4j_tpu.resilience import faults as _faults

log = logging.getLogger("deeplearning4j_tpu")


def _globalize(tree):
    """Orbax's multiprocess contract: every ``jax.Array`` it serializes
    must be a GLOBAL array (each process holding only its addressable
    shards).  Fully-addressable leaves — counters, the PRNG stream key,
    any single-device scalar — are process-local values, replicated by
    construction in the synchronous loop, so they serialize as numpy
    (orbax writes those from the primary host) and restore bit-exactly
    on every rank.  Single-process: identity."""
    import jax
    if jax.process_count() == 1:
        return tree
    import numpy as np

    def conv(v):
        if isinstance(v, jax.Array) and v.is_fully_addressable:
            return np.asarray(v)
        return v
    return jax.tree_util.tree_map(conv, tree)


_SAVES = telemetry.counter(
    "checkpoint_saves_total", "sharded checkpoint saves initiated")
_FAILURES = telemetry.counter(
    "checkpoint_failures_total",
    "periodic checkpoint saves that raised (training continued)")


class ShardedCheckpointer:
    """``save(step, state)`` / ``restore_latest(like)`` with keep-K
    rotation and async writes (preemption safety: the previous save
    completes or is discarded atomically by orbax)."""

    def __init__(self, directory, keep_last: int = 3, async_save: bool = True,
                 world: Optional[int] = None):
        if ocp is None:
            raise ImportError(
                "ShardedCheckpointer requires orbax-checkpoint, which "
                "failed to import in this environment: "
                f"{_ORBAX_IMPORT_ERROR!r}")
        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        # the LOGICAL world size recorded beside every save (default:
        # the process count).  A resuming fleet compares it against its
        # own world to detect an elastic shrink/grow — single-process
        # trainers whose world is a virtual-device mesh (stage count,
        # DP ways) can state it explicitly.
        self.world = None if world is None else int(world)
        opts = ocp.CheckpointManagerOptions(
            max_to_keep=keep_last,
            enable_async_checkpointing=async_save,
        )
        # the handler is declared up front: a manager that has not
        # saved or restored yet otherwise cannot type the item, and
        # item_metadata() (the sidecar-less elastic path) reads None
        self._mgr = ocp.CheckpointManager(
            self.directory, options=opts,
            item_handlers=ocp.StandardCheckpointHandler())

    # -- world/layout sidecar -------------------------------------------
    # Orbax owns the array bytes; the few scalars elastic resume needs
    # BEFORE a template can even be built (what world saved this step?
    # which optimizer layout is inside?) live in a tiny JSON beside the
    # step so a differently-shaped resumer can read them first.
    def _world_path(self, step: int) -> Path:
        return self.directory / f"world_{int(step)}.json"

    def _world_meta(self, state: Any) -> dict:
        import jax
        meta = {"world": (self.world if self.world is not None
                          else jax.process_count()),
                "processes": jax.process_count(),
                "devices": jax.device_count()}
        opt = state.get("opt_state") if isinstance(state, dict) else None
        layout = _elastic.opt_layout(opt)
        if layout is not None:
            meta["opt_layout"] = layout
        if layout == "pipe":
            run = _elastic.find_pipe_run(opt)
            if run is not None:
                meta["pipe_run"] = list(run)
        return meta

    def world_at(self, step) -> Optional[dict]:
        """The world/layout metadata recorded when ``step`` was saved
        (``{"world", "processes", "devices", "opt_layout", ...}``), or
        None for pre-elastic checkpoints."""
        if step is None:
            return None
        try:
            with open(self._world_path(step)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def save(self, step: int, state: Any, metrics: Optional[dict] = None,
             force: bool = False):
        # chaos site: simulated shard-write failure for THIS step label
        _faults.maybe_fail("checkpoint_fail", int(step))
        _SAVES.inc()
        self._mgr.save(int(step),
                       args=ocp.args.StandardSave(_globalize(state)),
                       metrics=metrics, force=force)
        import jax
        if jax.process_index() == 0:
            # best-effort sidecar (tiny, atomic via rename): a missing
            # one only degrades elastic detection to "unknown world"
            try:
                tmp = self._world_path(step).with_suffix(".tmp")
                tmp.write_text(json.dumps(self._world_meta(state)))
                os.replace(tmp, self._world_path(step))
            except OSError:
                log.exception("world sidecar write for step %d failed",
                              step)

    def restore_latest(self, like: Any):
        """Restore the newest step into the structure of `like` (sharded
        arrays are restored with their shardings).  Returns (step, state)
        or (None, None) when no checkpoint exists.

        ELASTIC: when the checkpoint was written by a differently-shaped
        trainer (pipeline stages vs. plain — the optimizer state's
        layout differs structurally), the restore retries with the
        saved layout's template, then re-lays the optimizer state into
        ``like``'s layout (``parallel.elastic``; byte-preserving per
        layer).  Plain world-size changes (DP N→M, stage repartition at
        the same layout) need no retry at all: orbax re-lays global
        arrays onto whatever shardings the template carries."""
        step = self._mgr.latest_step()
        if step is None:
            return None, None
        try:
            state = self._mgr.restore(
                step, args=ocp.args.StandardRestore(_globalize(like)))
            return step, state
        except Exception as orig:
            alt = self._alternate_template(like, step)
            if alt is None:
                raise
            try:
                state = self._mgr.restore(
                    step, args=ocp.args.StandardRestore(_globalize(alt)))
            except Exception:
                # the cross-layout retry did not help: the FIRST
                # failure is the real one (e.g. a transient I/O error
                # that only looked like a structure mismatch) — never
                # mask it with the retry's secondary error
                raise orig
            converted = _elastic.convert_opt_layout(
                state["opt_state"], like["opt_state"])
            if converted is None:       # pragma: no cover - defensive
                raise orig
            state["opt_state"] = converted
            log.info("elastic restore at step %d: optimizer state "
                     "re-laid from the saved %r layout into the "
                     "resuming trainer's %r layout (saved world=%s)",
                     step, self._saved_opt_layout(step)[0],
                     _elastic.opt_layout(like["opt_state"]),
                     (self.world_at(step) or {}).get("world"))
            return step, state

    def _saved_opt_layout(self, step: int):
        """``(layout, pipe_run)`` of the optimizer state saved at
        ``step`` — from the world sidecar when present, else derived
        structurally from the orbax metadata tree (shapes only, no
        array reads), so a lost/failed sidecar write degrades elastic
        DETECTION (world comparison) but never elastic RESTORE."""
        meta = self.world_at(step) or {}
        layout = meta.get("opt_layout")
        if layout is not None:
            run = meta.get("pipe_run")
            return layout, (tuple(int(v) for v in run) if run else None)
        saved_opt = self._mgr.item_metadata(step).tree.get("opt_state")
        layout = _elastic.opt_layout(saved_opt)
        run = (_elastic.find_pipe_run(saved_opt)
               if layout == "pipe" else None)
        return layout, run

    def _alternate_template(self, like: Any, step: int):
        """A restore template in the SAVED optimizer layout, built by
        re-laying ``like``'s own optimizer template — or None when no
        cross-layout restore applies (then the original error stands)."""
        if not isinstance(like, dict) or "opt_state" not in like:
            return None
        mine = _elastic.opt_layout(like["opt_state"])
        saved, run = self._saved_opt_layout(step)
        if mine == "pipe" and saved != "pipe":
            # saved per-layer (or unknowable, where per-layer is the
            # only other layout this pair of trainers produces)
            return {**like,
                    "opt_state": _elastic.pipe_to_layers(
                        like["opt_state"])}
        if mine == "layers" and saved == "pipe" and run:
            lo, hi = run
            return {**like,
                    "opt_state": _elastic.layers_to_pipe(
                        like["opt_state"], int(lo), int(hi))}
        return None

    def all_steps(self):
        return list(self._mgr.all_steps())

    def delete_step(self, step: int):
        """Drop one checkpoint step — the fleet-agreement primitive:
        a rank holding a step its peers lack (e.g. a forced final save
        that landed on some hosts only) discards it so every rank's
        ``restore_latest`` resolves to the agreed common step."""
        self._mgr.delete(int(step))
        try:
            self._world_path(step).unlink()
        except OSError:
            pass

    def wait(self):
        """Block until pending async saves land (call before exit)."""
        self._mgr.wait_until_finished()

    def close(self):
        self._mgr.close()


class CheckpointListener(TrainingListener):
    """Every-N-iterations / every-N-epochs checkpointing listener — the
    DL4J ``CheckpointListener`` surface on the sharded checkpointer."""

    def __init__(self, directory, save_every_n_iterations: Optional[int] = None,
                 save_every_n_epochs: Optional[int] = None, keep_last: int = 3,
                 async_save: bool = True, world: Optional[int] = None):
        self.ckpt = ShardedCheckpointer(directory, keep_last=keep_last,
                                        async_save=async_save, world=world)
        self.every_iter = save_every_n_iterations
        self.every_epoch = save_every_n_epochs
        self.world_at = self.ckpt.world_at   # elastic-resume delegate
        # Last orbax step label saved by THIS listener: when an epoch
        # boundary coincides with an every-N iteration, both hooks would
        # target the same step and orbax raises StepAlreadyExistsError.
        self._last_saved_step: Optional[int] = None

    def _state(self, model, completed_iterations=None):
        # counters.iteration stores ITERATIONS COMPLETED: listeners fire
        # after the update for `iteration` lands but before the counter
        # increments, so resuming with the raw counter would redo that
        # step on post-step params and diverge from the uninterrupted
        # loss trajectory (proven by test_preemption_kill_and_resume).
        it = (completed_iterations if completed_iterations is not None
              else model.iteration_count)
        hook = getattr(model, "_param_sync_hook", None)
        if hook is not None:   # lazily-synced trainer-owned params
            hook()
            sync_opt = getattr(hook, "sync_opt", None)
            if sync_opt is not None:
                # pipeline trainer: the live optimizer state is the
                # pipe-structured trainer-side tree — capture it so the
                # checkpoint can resume the pipeline path exactly
                sync_opt()
        state = {"params": model.params_tree,
                 "opt_state": model.opt_state,
                 "model_state": model.state_tree,
                 "counters": {"iteration": it,
                              "epoch": model.epoch_count,
                              # completed batches within the current
                              # epoch: run_fit fast-forwards the
                              # iterator past exactly this many on
                              # resume, so the continuation replays
                              # nothing and skips nothing
                              "batch_in_epoch": int(getattr(
                                  model, "batch_in_epoch", 0))}}
        rng = getattr(model, "_rng", None)
        if rng is not None:
            # the key STREAM position, so resumed dropout masks etc.
            # match the uninterrupted run's draw-for-draw
            state["rng"] = rng.state()
        return state

    def _try_save(self, step: int, state, metrics=None, force=False):
        """Periodic saves are best-effort: a failed write (full disk,
        flaky GCS, injected chaos) must not kill a healthy training
        run — it costs recovery granularity, which is exactly what
        ``checkpoint_failures_total`` alarms on.  Returns True when
        the save was initiated."""
        try:
            self.ckpt.save(step, state, metrics=metrics, force=force)
            return True
        except Exception:
            _FAILURES.inc()
            log.exception("checkpoint save at step %d failed; training "
                          "continues (previous checkpoints intact)", step)
            return False

    def iteration_done(self, model, iteration, epoch, loss):
        if self.every_iter and iteration > 0 and \
                iteration % self.every_iter == 0:
            # orbax step label = the iteration the checkpoint was taken
            # at; the stored counter = iteration + 1 (completed).
            if self._try_save(iteration, self._state(model, iteration + 1),
                              metrics={"loss": float(loss)}):
                self._last_saved_step = iteration

    def on_epoch_end(self, model, epoch):
        if self.every_epoch and (epoch + 1) % self.every_epoch == 0 \
                and model.iteration_count > 0:
            # Same labeling contract as the iteration path: orbax step =
            # last completed iteration index, stored counter = completed
            # count (= step + 1).  Keeps the two paths from colliding on
            # one step label with different counters.
            step = model.iteration_count - 1
            # Skip when this step is already checkpointed — by the
            # iteration hook this session, or persisted on disk by a
            # pre-preemption run (a fresh listener's in-memory marker is
            # empty, but the orbax directory isn't).
            if step == self._last_saved_step or step in self.ckpt.all_steps():
                return
            if self._try_save(step, self._state(model)):
                self._last_saved_step = step

    @staticmethod
    def _apply_trees(model, state):
        """Overwrite the model's params/opt/model-state trees from a
        restored checkpoint, disarming any deferred pipeline unstack
        (hook protocol defined in parallel/trainer.py) so it cannot
        clobber the restored weights."""
        model.params_tree = state["params"]
        model.opt_state = state["opt_state"]
        model.state_tree = state["model_state"]
        discard = getattr(getattr(model, "_param_sync_hook", None),
                          "discard_pending", None)
        if discard is not None:
            discard()

    def restore_params_into(self, model):
        """Restore ONLY the parameter/optimizer/model-state trees from
        the newest checkpoint, leaving counters, batch position, and
        the RNG stream at their CURRENT values — the rollback
        primitive: after a divergence, training resumes from the last
        good weights but keeps moving FORWARD through the data stream
        (rewinding the live iterator is impossible in general, and
        rewinding the counters without it would desynchronize every
        later checkpoint's resume bookkeeping and collide orbax step
        labels).  Returns the restored step or None."""
        step, state = self.ckpt.restore_latest(self._state(model))
        if step is None:
            return None
        self._apply_trees(model, state)
        return step

    def restore_into(self, model):
        """Resume a model in place from the newest checkpoint; returns the
        restored step or None."""
        like = self._state(model)
        try:
            step, state = self.ckpt.restore_latest(like)
        except Exception:
            # checkpoints written before the resilience layer lack the
            # rng leaf / batch_in_epoch counter; retry with the legacy
            # template so old runs stay resumable (counters fall back
            # to epoch-start, rng to the fresh stream)
            legacy = {k: v for k, v in like.items() if k != "rng"}
            legacy["counters"] = {
                k: v for k, v in like["counters"].items()
                if k != "batch_in_epoch"}
            step, state = self.ckpt.restore_latest(legacy)
            log.warning("restored a pre-resilience checkpoint (step %s):"
                        " no rng/batch position — resume is epoch-"
                        "aligned, not batch-exact", step)
        if step is None:
            return None
        self._apply_trees(model, state)
        model.iteration_count = int(state["counters"]["iteration"])
        model.epoch_count = int(state["counters"]["epoch"])
        model.batch_in_epoch = int(
            state["counters"].get("batch_in_epoch", 0))
        if "rng" in state and getattr(model, "_rng", None) is not None:
            model._rng.set_state(state["rng"])
        return step
