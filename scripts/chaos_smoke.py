#!/usr/bin/env python
"""Chaos smoke — the resilience-layer CI gate.

Fires every :data:`deeplearning4j_tpu.resilience.FAULT_KINDS` injector
kind against a real (tiny, CPU-sized) training run and a real
``GenerationServer``, then asserts:

* training still completes with the uninterrupted run's EXACT final
  loss and parameters (kill-and-resume is bit-identical; NaN steps are
  skipped; a failed checkpoint write degrades, not kills);
* a PIPELINE trainer preempted under ``fleet_resume_fit`` rendezvouses,
  agrees a resume step, restacks the restored tree into the
  pipe-sharded params and finishes (coordinated-restart + pipeline
  resume, in the single-process degenerate);
* decode-server recovery is ZERO-DOWNTIME: a scheduler crash salvages
  every in-flight slot's KV (all callers complete byte-identically,
  nothing resubmitted), and a stuck tick with a poisoned slot drops
  ONLY that slot — the two unaffected callers finish offline-identical
  and the implicated one rides a submit retry through;
* a SAMPLED SPECULATIVE slot (ISSUE 20) survives the same tick crash:
  the watchdog salvages its draft table and held residual/PRNG state
  — the same-seed sampled stream completes byte-identical to the
  uncrashed run, its greedy pool neighbour offline-identical;
* a MESH-SHARDED tp=2 replica (ISSUE 17) survives the same tick crash
  — the unchanged watchdog salvages every slot into the rebuilt
  sharded pool (byte-identical, ``tp_device_loss`` flight event on
  the wire) — and a mixed fleet whose tp=2 replica is killed
  mid-decode migrates every request byte-identical onto the
  single-chip survivor (``outcome="migrated"`` on the scrape);
* a DISAGGREGATED fleet (prefill + decode roles, ISSUE 14) survives a
  SIGKILL of its prefill replica mid-handoff: the staged requests
  re-place through the existing migration machinery onto the decode
  survivor and complete byte-identical to offline ``generate()``;
* an induced OVERLOAD STORM (ISSUE 18) walks the production front
  door end to end: the admission projection sheds the batch tenant
  with a server-advised retry-after, the degradation ladder climbs to
  the shed rung and walks back down once the burn clears, interactive
  traffic rides through with zero deadline misses (degraded outputs
  byte-identical to the capped offline prefix), a near-deadline
  request races a hedge whose loser is cancelled, and the whole
  ladder walk is replayed from the recorded TSDB history over
  ``/query``;
* every recovery event landed in the telemetry registry
  (``faults_injected_total{kind=...}`` for each kind, resume/preempt/
  bad-step/watchdog counters, ``fleet_*`` + ``kv_slots_*`` counters,
  submit retry histograms) — checked over a real HTTP scrape via the
  helpers in ``check_telemetry.py``.

Runs on CPU inside the tier-1 budget — wired into
``tests/test_resilience.py::test_chaos_smoke`` un-marked, and runnable
standalone:

    JAX_PLATFORMS=cpu python scripts/chaos_smoke.py
"""
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.request

# the pipeline chaos run needs >= 2 devices; force a virtual CPU pair
# BEFORE jax initializes (no-op in-process under tests/conftest.py,
# which already forces 8)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

# each training-side kind once, at deterministic iterations of a
# 3-epoch x 6-batch run (18 iterations; checkpoints every 2)
TRAIN_PLAN = ["data_stall@1:0.05", "nan_loss@3", "checkpoint_fail@4",
              "step_exception@7", "preempt@12"]
# serving scenario 1 — scheduler crash mid-service: enqueue window,
# 4 throttled passes (every slot fills and decodes a few ticks), then
# pass 5 kills the scheduler thread.  Scenario 2 — stuck tick with a
# poisoned slot: 15 throttled passes (budgets stay un-drained while
# the main thread NaN-poisons the victim's KV row), then pass 16
# hangs past the 0.8s deadline -> watchdog salvage recovery.
from deeplearning4j_tpu.resilience.faults import (poison_slot_kv,
                                                  throttled_stall_plan)

SERVE_CRASH_PLAN = throttled_stall_plan(4, "serve_tick_fail@5")
SERVE_STALL_PLAN = throttled_stall_plan(15, "serve_tick_stall@16:2.2")
# serving scenario 3 (ISSUE 17) — the SAME crash shape against a tp=2
# MESH-SHARDED server: from the host a failed dispatch on a multi-chip
# replica is indistinguishable from losing one chip of the tp group
# mid-tick, so the unchanged watchdog must salvage the sharded pool
# and the tp_device_loss flight event must land with the slice
SERVE_TP_CRASH_PLAN = throttled_stall_plan(4, "serve_tick_fail@5")
# serving scenario (ISSUE 20) — the crash shape against a SAMPLED
# speculative server (fixed K: byte pins need replayable depth)
SERVE_SPEC_CRASH_PLAN = throttled_stall_plan(4, "serve_tick_fail@5")


def _load_check_telemetry():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "check_telemetry.py")
    spec = importlib.util.spec_from_file_location("check_telemetry", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(min_history_s: float = 60.0) -> int:
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration, resilience,
                                    telemetry)
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterator import ListDataSetIterator
    from deeplearning4j_tpu.models.generation import TransformerGenerator
    from deeplearning4j_tpu.nn.conf.layers_core import (DenseLayer,
                                                        OutputLayer)
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.parallel import (CheckpointListener,
                                             GenerationServer)
    from deeplearning4j_tpu.resilience import (BadStepPolicy,
                                               FaultInjector,
                                               InjectedFault,
                                               auto_resume_fit)
    from deeplearning4j_tpu.zoo.gpt import Gpt

    ct = _load_check_telemetry()
    registry = telemetry.get_registry()
    problems = []
    # ISSUE 16: record the whole run into the embedded time-series
    # store at beacon cadence — the SLO kill at the end must find
    # >= min_history_s of pre-crash history in its bundle, and the
    # live /query read must reproduce the burn window
    tsdb = telemetry.get_tsdb()
    tsdb.start_recorder(registry, interval_s=1.0)

    def counter(name):
        return registry.counter(name)

    fault_counter = registry.counter("faults_injected_total",
                                     labelnames=("kind",))

    def model():
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Adam(learning_rate=1e-2)).list()
                .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
                .layer(OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    x = rng.normal(size=(96, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 96)]

    def data():
        return ListDataSetIterator(DataSet(x, y).batch_by(16))

    # -- uninterrupted reference ---------------------------------------
    ref = model()
    ref_loss = ref.fit(data(), n_epochs=3, async_prefetch=False)

    # -- training fault matrix -----------------------------------------
    faults_before = {k: fault_counter.labels(kind=k).value
                     for k in resilience.FAULT_KINDS}
    resumes0 = counter("train_resumes_total").value
    preempts0 = counter("train_preemptions_total").value
    skipped0 = counter("bad_steps_skipped_total").value
    ckfail0 = counter("checkpoint_failures_total").value

    m = model()
    with tempfile.TemporaryDirectory() as d:
        ck = CheckpointListener(os.path.join(d, "ck"),
                                save_every_n_iterations=2)
        m.set_listeners(ck, BadStepPolicy(max_consecutive=3,
                                          checkpoint=ck))
        with FaultInjector(TRAIN_PLAN):
            loss = auto_resume_fit(
                lambda: m.fit(data(), n_epochs=3, async_prefetch=False,
                              resume=True),
                max_restarts=4, retry_on=(InjectedFault,))
        ck.ckpt.close()
    if m.epoch_count != 3:
        problems.append(f"training finished {m.epoch_count}/3 epochs")
    if loss is None or not np.isfinite(loss):
        problems.append(f"post-chaos final loss {loss}")
    if counter("train_resumes_total").value - resumes0 < 2:
        problems.append("expected >= 2 checkpoint resumes "
                        "(step_exception + preempt restarts)")
    if counter("train_preemptions_total").value - preempts0 != 1:
        problems.append("train_preemptions_total did not grow by 1")
    if counter("bad_steps_skipped_total").value - skipped0 != 1:
        problems.append("bad_steps_skipped_total did not grow by 1")
    if counter("checkpoint_failures_total").value - ckfail0 != 1:
        problems.append("checkpoint_failures_total did not grow by 1")

    # -- preempt-only: kill-and-resume must be BIT-IDENTICAL -----------
    # (the combined matrix above legitimately diverges from the
    # reference: its NaN-poisoned update is skipped where the
    # uninterrupted run applied the clean one)
    m2 = model()
    with tempfile.TemporaryDirectory() as d:
        ck2 = CheckpointListener(os.path.join(d, "ck"),
                                 save_every_n_iterations=5)
        m2.set_listeners(ck2)
        with FaultInjector(["preempt@8"]):
            loss2 = auto_resume_fit(
                lambda: m2.fit(data(), n_epochs=3, async_prefetch=False,
                               resume=True), max_restarts=2)
        ck2.ckpt.close()
    if loss2 is None or float(loss2) != float(ref_loss):
        problems.append(
            f"preempt+resume final loss {loss2} != uninterrupted "
            f"{ref_loss} (kill-and-resume not bit-identical)")

    # -- preempt-in-pipeline: coordinated fleet restart + pipeline
    # resume (single-process degenerate of the multiproc chaos test) --
    import jax
    fleet_resumed = registry.counter(
        "fleet_resumes_total",
        labelnames=("outcome",)).labels(outcome="resumed")
    fleet_shrink = registry.counter(
        "fleet_elastic_resumes_total",
        labelnames=("direction",)).labels(direction="shrink")
    fleet_b0 = counter("fleet_preempt_broadcasts_total").value
    fleet_r0 = fleet_resumed.value
    if jax.device_count() < 2:
        problems.append(f"pipeline chaos run needs >= 2 devices, have "
                        f"{jax.device_count()}")
    else:
        from deeplearning4j_tpu.parallel.mesh import MeshConfig
        from deeplearning4j_tpu.parallel.trainer import ShardedTrainer
        from deeplearning4j_tpu.resilience import fleet_resume_fit
        rng_p = np.random.default_rng(4)
        px = rng_p.integers(0, 32, (16, 8)).astype(np.int32)
        py = np.roll(px, -1, axis=1)
        gpt_p = Gpt(vocab_size=32, max_len=8, d_model=16, n_layers=2,
                    n_heads=2, d_ff=32, seq_len=8, compute_dtype=None,
                    use_flash=False, seed=9).init_graph()
        tr_p = ShardedTrainer(gpt_p, MeshConfig(pipeline=2), n_micro=2)

        def data_p():
            return ListDataSetIterator(DataSet(px, py).batch_by(8))

        with tempfile.TemporaryDirectory() as d:
            # world=2 recorded beside every save: the shrink scenario
            # below resumes the SAME checkpoints on a 1-way world
            ck_p = CheckpointListener(os.path.join(d, "ck"),
                                      save_every_n_iterations=2,
                                      world=2)
            gpt_p.set_listeners(ck_p)
            with FaultInjector(["preempt@2"]):
                loss_p = fleet_resume_fit(
                    lambda: tr_p.fit(data_p(), n_epochs=2, resume=True),
                    mesh=tr_p.mesh, checkpoint=ck_p, max_restarts=2,
                    world=2)
            ck_p.ckpt.wait()
            if gpt_p.epoch_count != 2:
                problems.append(f"pipeline chaos run finished "
                                f"{gpt_p.epoch_count}/2 epochs")
            if loss_p is None or not np.isfinite(loss_p):
                problems.append(f"pipeline post-preempt loss {loss_p}")

            # -- ELASTIC SHRINK (ISSUE 10): the 2-stage pipeline run's
            # checkpoints (pipe-structured optimizer state, recorded
            # world=2) resume on a PLAIN 1-way trainer — the restore
            # path unstacks the optimizer layout byte-preserving and
            # the shrink is counted on the wire ---------------------
            s0 = fleet_shrink.value
            gpt_s = Gpt(vocab_size=32, max_len=8, d_model=16,
                        n_layers=2, n_heads=2, d_ff=32, seq_len=8,
                        compute_dtype=None, use_flash=False,
                        seed=9).init_graph()
            tr_s = ShardedTrainer(gpt_s, MeshConfig(data=1))
            ck_s = CheckpointListener(os.path.join(d, "ck"), world=1)
            gpt_s.set_listeners(ck_s)
            loss_s = fleet_resume_fit(
                lambda: tr_s.fit(data_p(), n_epochs=3, resume=True),
                mesh=tr_s.mesh, checkpoint=ck_s, max_restarts=1,
                world=1)
            ck_s.ckpt.close()
            if gpt_s.epoch_count != 3:
                problems.append(f"elastic shrink resume finished "
                                f"{gpt_s.epoch_count}/3 epochs")
            if loss_s is None or not np.isfinite(loss_s):
                problems.append(f"elastic shrink resume loss {loss_s}")
            if fleet_shrink.value - s0 < 1:
                problems.append("2-stage checkpoint resumed on the "
                                "1-way trainer counted no elastic "
                                "shrink")
            ck_p.ckpt.close()
        if counter("fleet_preempt_broadcasts_total").value - fleet_b0 < 1:
            problems.append("fleet_preempt_broadcasts_total did not grow")
        if fleet_resumed.value - fleet_r0 < 1:
            problems.append("fleet_resumes_total did not grow")

    # -- serving fault matrix: zero-downtime KV salvage ----------------
    wd0 = counter("serve_watchdog_restarts_total").value
    salv0 = counter("kv_slots_salvaged_total").value
    drop0 = counter("kv_slots_dropped_total").value
    gpt = Gpt(vocab_size=50, max_len=32, d_model=32, n_layers=2,
              n_heads=4, d_ff=64, seq_len=8, compute_dtype=None,
              seed=3).init_graph()
    offline = TransformerGenerator(gpt)
    p = np.asarray([1, 2, 3, 4], np.int32)

    # one 3-slot server takes both hits in sequence.  tick_batch=1
    # pins the single-tick watchdog deadline this matrix injects
    # against (a fused K-tick scan legitimately stretches the deadline
    # by K and would absorb the stall as a slow scan).  The deadline
    # is ARMED only after the warm submit: the first-dispatch compile
    # runs 1-2s on a loaded box, and a 0.8s deadline live during warm
    # fires a spurious recovery that skews every counter delta the
    # matrix asserts (the watchdog re-reads tick_timeout_s each pass,
    # so tightening it post-warm is race-free).
    with GenerationServer(gpt, n_slots=3, max_len=32, tick_timeout_s=30.0,
                          tick_batch=1,
                          submit_retries=4, retry_backoff_s=0.02) as srv:
        srv.submit(p, n_new=2, timeout=300)          # warm the compiles
        srv.tick_timeout_s = 0.8                     # arm the deadline

        # (1) scheduler crash with three requests mid-decode: the
        # watchdog salvages ALL slots' KV into the rebuilt pool — every
        # caller completes without resubmission, byte-identical
        ref24 = offline.generate(p[None], n_new=24)[0]
        with FaultInjector(SERVE_CRASH_PLAN):
            hs = [srv.submit_async(p, n_new=24) for _ in range(3)]
            for i, h in enumerate(hs):
                try:
                    if not np.array_equal(h.result(timeout=300), ref24):
                        problems.append(
                            f"post-crash salvage output {i} mismatch")
                except Exception as e:
                    problems.append(f"crash-salvaged request {i} "
                                    f"failed: {e}")
        if counter("kv_slots_salvaged_total").value - salv0 != 3:
            problems.append("crash recovery salvaged != 3 slots")
        if counter("kv_slots_dropped_total").value - drop0 != 0:
            problems.append("crash recovery dropped a slot")
        if not srv.healthy():
            problems.append("server not healthy after crash recovery")

        # (2) stuck tick with 2 live + 1 poisoned slot: recovery drops
        # ONLY the poisoned slot (its caller retries through); the two
        # unaffected callers finish offline-identical, un-resubmitted
        salv1 = counter("kv_slots_salvaged_total").value
        drop1 = counter("kv_slots_dropped_total").value
        ref20 = offline.generate(p[None], n_new=20)[0]
        victim_out = {}
        with FaultInjector(SERVE_STALL_PLAN):
            h0 = srv.submit_async(p, n_new=20)
            h1 = srv.submit_async(p, n_new=20)
            vt = threading.Thread(target=lambda: victim_out.update(
                v=srv.submit(p, n_new=20, timeout=300, retries=4)))
            vt.start()                    # third admission -> slot 2
            for _ in range(2000):
                with srv._lock:
                    n_act = len(srv._active)
                if n_act == 3:
                    break
                time.sleep(0.005)
            if n_act != 3:
                problems.append(f"stall scenario admitted {n_act}/3")
            with srv._lock:               # the victim thread's slot is
                victim_slot = [s for s, r in srv._active.items()
                               if r not in (h0, h1)][0]
            if not poison_slot_kv(srv, victim_slot):
                problems.append("could not poison the victim's KV row")
            for i, h in enumerate((h0, h1)):
                try:
                    if not np.array_equal(h.result(timeout=300), ref20):
                        problems.append(
                            f"post-stall salvage output {i} mismatch")
                except Exception as e:
                    problems.append(f"stall-salvaged request {i} "
                                    f"failed: {e}")
            vt.join(timeout=300)
        if not np.array_equal(victim_out.get("v"), ref20):
            problems.append("poisoned slot's retried submit mismatch")
        if counter("kv_slots_salvaged_total").value - salv1 != 2:
            problems.append("stall recovery salvaged != 2 slots")
        if counter("kv_slots_dropped_total").value - drop1 != 1:
            problems.append("stall recovery dropped != 1 slot")
    if counter("serve_watchdog_restarts_total").value - wd0 != 2:
        problems.append("expected exactly 2 watchdog restarts "
                        "(crash + stall)")

    # -- sampled speculative slot salvage (ISSUE 20): the same tick
    # crash against a SAMPLED speculative server.  The watchdog must
    # salvage the slot's target AND draft tables plus the held
    # residual/PRNG state leaves — proven the hard way: the salvaged
    # same-seed sampled stream is BYTE-IDENTICAL to the uncrashed run
    # (fixed K: adaptive depth decisions are host-side and not
    # replayed, so byte pins use a fixed-depth server), and the
    # greedy neighbour in the same mixed pool stays offline-identical.
    spec_salv0 = counter("kv_slots_salvaged_total").value
    spec_wd0 = counter("serve_watchdog_restarts_total").value
    spec_samp = {"temperature": 0.9, "top_k": 6, "seed": 21}
    ref20g = offline.generate(p[None], n_new=20)[0]
    # generous tick_timeout_s: the fault KILLS the scheduler thread
    # (watchdog detects death via is_alive, timeout-independent); a
    # tight stuck-tick deadline would spuriously re-recover during
    # the salvage path's sampled-spec recompiles on a loaded CPU
    with GenerationServer(gpt, n_slots=2, max_len=32,
                          tick_timeout_s=30.0, tick_batch=1,
                          submit_retries=4, retry_backoff_s=0.02,
                          speculative={"k": 2, "rounds": 1,
                                       "draft_layers": 1}) as ssrv:
        ssrv.submit(p, n_new=2, timeout=300)      # warm the compiles
        ref20s = ssrv.submit(p, n_new=20, sampling=dict(spec_samp),
                             timeout=300)         # uncrashed reference
        with FaultInjector(SERVE_SPEC_CRASH_PLAN):
            hg = ssrv.submit_async(p, n_new=20)
            hsamp = ssrv.submit_async(p, n_new=20,
                                      sampling=dict(spec_samp))
            try:
                if not np.array_equal(hg.result(timeout=300), ref20g):
                    problems.append("sampled-spec salvage: greedy "
                                    "neighbour diverged from offline")
                if not np.array_equal(hsamp.result(timeout=300),
                                      ref20s):
                    problems.append(
                        "sampled-spec salvage: same-seed stream not "
                        "byte-identical to the uncrashed run")
            except Exception as e:
                problems.append(f"sampled-spec salvaged request "
                                f"failed: {e}")
        if not ssrv.healthy():
            problems.append("sampled-spec server not healthy after "
                            "salvage")
    if counter("kv_slots_salvaged_total").value - spec_salv0 != 2:
        problems.append("sampled-spec recovery salvaged != 2 slots")
    if counter("serve_watchdog_restarts_total").value - spec_wd0 != 1:
        problems.append("sampled-spec recovery != 1 watchdog restart")

    # -- mesh-sharded replica (ISSUE 17): the same tick crash against
    # a tp=2 server.  The UNCHANGED watchdog salvages every slot's KV
    # into the rebuilt sharded pool — all three callers complete
    # byte-identical, nothing resubmitted — and the mesh-loss flight
    # event lands carrying the slice it spanned.
    tp_ev = registry.counter(
        "flight_events_total",
        labelnames=("kind",)).labels(kind="tp_device_loss")
    ev0 = tp_ev.value
    salv2 = counter("kv_slots_salvaged_total").value
    wd2 = counter("serve_watchdog_restarts_total").value
    with GenerationServer(gpt, n_slots=3, max_len=32,
                          tick_timeout_s=30.0, tick_batch=1,
                          submit_retries=4, retry_backoff_s=0.02,
                          devices=jax.devices()[:2]) as tsrv:
        if tsrv.stats()["tp"] != 2:
            problems.append("mesh chaos server did not build tp=2")
        tsrv.submit(p, n_new=2, timeout=300)     # warm the compiles
        tsrv.tick_timeout_s = 0.8        # arm post-warm (see matrix)
        with FaultInjector(SERVE_TP_CRASH_PLAN):
            hs_t = [tsrv.submit_async(p, n_new=24) for _ in range(3)]
            for i, h in enumerate(hs_t):
                try:
                    if not np.array_equal(h.result(timeout=300),
                                          ref24):
                        problems.append(
                            f"tp=2 crash salvage output {i} mismatch")
                except Exception as e:
                    problems.append(f"tp=2 crash-salvaged request {i} "
                                    f"failed: {e}")
        if not tsrv.healthy():
            problems.append("tp=2 server not healthy after recovery")
    if counter("kv_slots_salvaged_total").value - salv2 != 3:
        problems.append("tp=2 crash recovery salvaged != 3 slots")
    if counter("serve_watchdog_restarts_total").value - wd2 != 1:
        problems.append("tp=2 crash recovery != 1 watchdog restart")
    if tp_ev.value - ev0 < 1:
        problems.append("tp=2 tick crash recorded no tp_device_loss "
                        "flight event")

    # -- serving fleet: SIGKILL-equivalent death of one of two
    # replicas mid-decode.  The seed request warms one replica's
    # prefix cache so affinity routes all four follow-ups there
    # (2 decoding + 2 queued on the victim); the kill migrates every
    # one of them to the survivor, byte-identical to offline decode,
    # with the migrated outcome on the wire.  No FaultInjector here —
    # the fault-count matrix below stays exact. --------------------
    from deeplearning4j_tpu.serving import ServingFleet

    fleet_fam = registry.counter("fleet_requests_total",
                                 labelnames=("tenant", "outcome"))

    def outcome_total(outcome):
        return sum(c.value for vals, c in fleet_fam._items()
                   if vals[1] == outcome)

    mig0 = outcome_total("migrated")
    pf = np.arange(1, 14, dtype=np.int32)
    ref_fleet = offline.generate(pf[None], n_new=12)[0]
    with ServingFleet(gpt, n_replicas=2, n_slots=2, max_len=32,
                      block_size=4, tick_batch=1,
                      tick_timeout_s=None) as fleet:
        h_seed = fleet.submit_async(pf, n_new=2)
        h_seed.result(timeout=300)
        warm = h_seed.replica
        hs = [fleet.submit_async(pf, n_new=12) for _ in range(4)]
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if any(h.emitted > 0 for h in hs):
                break                    # mid-decode on the victim
            time.sleep(0.001)
        fleet.kill(warm)
        for i, h in enumerate(hs):
            try:
                if not np.array_equal(h.result(timeout=300),
                                      ref_fleet):
                    problems.append(
                        f"fleet migrated output {i} mismatch")
            except Exception as e:
                problems.append(f"fleet migrated request {i} "
                                f"failed: {e}")
        if fleet.stats()["healthy_replicas"] != 1:
            problems.append("fleet survivor count != 1 after kill")
        mig_trace = hs[0].trace_id
    if outcome_total("migrated") - mig0 < 1:
        problems.append("fleet kill produced no migrated requests")

    # -- mesh fleet (ISSUE 17): ONE fleet mixing a tp=2 replica and a
    # single-chip replica, the MULTI-CHIP one killed mid-decode —
    # every in-flight request migrates onto the single-chip survivor
    # and completes byte-identical (the sharded and unsharded ticks
    # are the same function by construction, so migrating across
    # topologies is invisible to the caller).  The affinity seed must
    # land on replica 0 (the tp=2 one) for the kill to catch work
    # mid-decode; the scenario retries on a fresh fleet when cold
    # placement sends it elsewhere, or when the short decode outruns
    # the kill and nothing was left to migrate.
    pm = np.arange(3, 16, dtype=np.int32)
    ref_mesh = offline.generate(pm[None], n_new=12)[0]
    migm0 = outcome_total("migrated")
    for attempt in range(3):
        with ServingFleet(gpt, n_replicas=2, n_slots=2, max_len=32,
                          block_size=4, tick_batch=1,
                          tick_timeout_s=None,
                          devices=[jax.devices()[:2], None]) as mflt:
            if mflt.replica(0).stats()["tp"] != 2 \
                    or mflt.replica(1).stats()["tp"] != 1:
                problems.append("mesh fleet replica topology wrong")
            h_seed = mflt.submit_async(pm, n_new=2)
            h_seed.result(timeout=300)
            if h_seed.replica != 0:
                continue             # need the tp=2 replica warm
            hs_m = [mflt.submit_async(pm, n_new=12) for _ in range(4)]
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if any(h.emitted > 0 for h in hs_m):
                    break            # mid-decode on the tp=2 replica
                time.sleep(0.001)
            mflt.kill(0)             # SIGKILL the multi-chip replica
            for i, h in enumerate(hs_m):
                try:
                    if not np.array_equal(h.result(timeout=300),
                                          ref_mesh):
                        problems.append(
                            f"mesh fleet migrated output {i} mismatch")
                except Exception as e:
                    problems.append(f"mesh fleet migrated request {i} "
                                    f"failed: {e}")
            if mflt.stats()["healthy_replicas"] != 1:
                problems.append("mesh fleet survivor count != 1 "
                                "after the tp=2 replica kill")
            if mflt.replica(1).stats()["tp"] != 1:
                problems.append("mesh fleet survivor is not the "
                                "single-chip replica")
        if outcome_total("migrated") - migm0 >= 1:
            break                    # the kill landed mid-decode
    else:
        problems.append("tp=2 replica kill never migrated a request "
                        "(3 attempts)")

    # -- disaggregated prefill/decode (ISSUE 14): kill the PREFILL
    # replica with long-prompt requests staged on it mid-handoff —
    # every request re-places through the EXISTING migration
    # machinery (reclassified direct against the surviving decode
    # replica, since no prefill replica remains) and completes
    # byte-identical to offline generate(); the migrated outcome is
    # asserted on the real scrape at the bottom.  The kill races the
    # (fast) prefill stage, so the scenario retries on a fresh fleet
    # until the kill lands while >= 1 request is still placed on the
    # prefill replica.
    base9 = np.arange(1, 10, dtype=np.int32)
    d_longs = [np.concatenate([base9, np.asarray(
        [i + 1, i + 2, i + 3, i + 4], np.int32)]) for i in range(3)]
    d_refs = [offline.generate(p[None], n_new=8)[0] for p in d_longs]
    migd0 = outcome_total("migrated")
    for attempt in range(3):
        with ServingFleet(gpt, n_replicas=2,
                          roles=("prefill", "decode"), n_slots=2,
                          max_len=32, block_size=4, tick_batch=1,
                          tick_timeout_s=None) as dfleet:
            # one clean round trip first: prefill -> handoff -> decode
            out_d = dfleet.submit(d_longs[0], n_new=8, timeout=300)
            if not np.array_equal(out_d, d_refs[0]):
                problems.append("disagg decode diverged from offline "
                                "generate() pre-kill")
            if dfleet.replica(1).stats()["tier_fetches"] < 1:
                problems.append("disagg handoff restored no blocks on "
                                "the decode replica")
            hs_d = [dfleet.submit_async(p, n_new=8)
                    for p in d_longs[1:]]
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if any(h.replica == 0 for h in hs_d):
                    break            # staged on the prefill replica
                if all(h.done() for h in hs_d):
                    break            # lost the race outright: don't
                                     # burn the deadline, just retry
                time.sleep(0.0005)
            dfleet.kill(0)           # SIGKILL the prefill replica
            for i, h in enumerate(hs_d):
                try:
                    if not np.array_equal(h.result(timeout=300),
                                          d_refs[1 + i]):
                        problems.append(
                            f"disagg migrated output {i} mismatch")
                except Exception as e:
                    problems.append(f"disagg migrated request {i} "
                                    f"failed: {e}")
            if dfleet.stats()["healthy_replicas"] != 1:
                problems.append("disagg fleet survivor count != 1 "
                                "after the prefill-replica kill")
        if outcome_total("migrated") - migd0 >= 1:
            break                    # the kill landed mid-handoff
    else:
        problems.append("prefill-replica kill never migrated a "
                        "request (3 attempts)")

    # -- cross-worker trace store (ISSUE 13): the killed replica's
    # request crossed placements mid-decode — its spans (abandoned
    # victim placement INCLUDED, flushed by the owner-death path)
    # must beacon and stitch into exactly ONE submit -> retire tree,
    # not the disjoint fragments PR 12 left behind ------------------
    with tempfile.TemporaryDirectory() as td:
        telemetry.publish_beacon(
            td, "chaoshost", registry=registry,
            trace_events=telemetry.get_tracer().trace_events())
        fr = telemetry.FleetRegistry(td, stale_after_s=3600.0)
        fr.refresh()
        tree = fr.traces.tree(mig_trace)
    if tree["root"] is None:
        problems.append("kill-mid-decode trace has no stitched root "
                        f"(trace {mig_trace})")
    else:
        def _count(node, name):
            return ((node["name"] == name)
                    + sum(_count(c, name) for c in node["children"]))
        if tree["orphans"]:
            problems.append(
                "kill-mid-decode trace left orphan fragments: "
                f"{[n['name'] for n in tree['orphans']]}")
        if _count(tree["root"], "request/placement") < 2:
            problems.append(
                "migrated request's tree holds < 2 placement spans "
                "(victim + failover) — the recovery fragment was "
                "lost")

    # -- closed-loop autoscaler (ISSUE 12 + 13): the step load on a
    # 1-replica fleet must now scale 1 -> 2 PREDICTIVELY — the
    # backlog jump's growth rate projects a queue_depth_high breach
    # inside the horizon and pre-warms the replica while every
    # reactive signal is still quiet (the 1s wait target CANNOT have
    # tripped before 1s of queueing even existed; the forecast fires
    # within the first few 0.05s evaluations) — then back 2 -> 1 once
    # the load drains, with ZERO interactive deadline misses.
    # Asserted from the real scrape at the bottom: the pre-warm
    # counter only increments when the up action's reasons were
    # forecast-ONLY, so prewarms >= 1 IS "replica added before the
    # reactive breach signal".
    from deeplearning4j_tpu.serving import AutoscalePolicy, Autoscaler
    as_actions = registry.counter("fleet_autoscale_actions_total",
                                  labelnames=("direction",))
    prewarms = registry.counter("fleet_autoscale_prewarms_total")
    up0 = as_actions.labels(direction="up").value
    down0 = as_actions.labels(direction="down").value
    pw0 = prewarms.value
    fleet2 = ServingFleet(gpt, n_replicas=1, n_slots=2, max_len=32,
                          block_size=4, tick_batch=1,
                          tick_timeout_s=None)
    pol = AutoscalePolicy(min_replicas=1, max_replicas=2,
                          queue_wait_p99_target_s=1.0,
                          queue_depth_high=64,
                          forecast_horizon_s=60.0,
                          forecast_window_s=2.0,
                          forecast_min_points=3,
                          up_consecutive=2, down_consecutive=4,
                          cooldown_s=0.3)
    scaler = Autoscaler(fleet2, pol, interval_s=0.05,
                        tenant_classes={"analytics": "batch"}).start()
    try:
        pa = np.asarray([1, 2, 3, 4], np.int32)
        fleet2.submit(pa, n_new=2, tenant="inter", timeout=300)
        hs2 = [fleet2.submit_async(pa, n_new=24, tenant="inter",
                                   deadline_s=300.0)
               for _ in range(40)]
        for i, h in enumerate(hs2):
            try:
                h.result(timeout=300)
            except Exception as e:
                problems.append(f"step-load request {i} failed: {e}")
        drain_by = time.monotonic() + 120
        while time.monotonic() < drain_by and scaler.target > 1:
            time.sleep(0.05)
    finally:
        scaler.close()
    if as_actions.labels(direction="up").value - up0 < 1:
        problems.append("step load did not autoscale 1 -> 2")
    if prewarms.value - pw0 < 1:
        problems.append(
            "step load scaled up REACTIVELY — the forecast did not "
            "pre-warm the replica before an SLO signal tripped")
    if as_actions.labels(direction="down").value - down0 < 1:
        problems.append("drained fleet did not autoscale 2 -> 1")
    if scaler.target != 1:
        problems.append(f"autoscaler target settled at {scaler.target}"
                        " != 1")
    if fleet2.stats()["healthy_replicas"] != 1:
        problems.append("fleet healthy_replicas != 1 after scale-in")
    fleet2.shutdown(drain=True)

    # -- SLO error-budget closed loop + kill under pressure (ISSUE
    # 15): induced overload -> the burn-rate alert fires on the
    # AGGREGATED scrape BEFORE any interactive deadline miss -> the
    # autoscaler pre-warm is attributed to the ALERT signal
    # (fleet_autoscale_alert_prewarms_total) -> a replica SIGKILL
    # mid-storm yields EXACTLY ONE postmortem bundle whose merged
    # timeline (scripts/postmortem.py) holds the victim's final
    # dispatch events, its open spans and the alert state. --------
    from deeplearning4j_tpu.telemetry import flightrec
    from deeplearning4j_tpu.telemetry.slo import AlertEngine, SLOSpec

    def _load_postmortem():
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "postmortem.py")
        spec = importlib.util.spec_from_file_location("postmortem",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    alert_prewarms = counter("fleet_autoscale_alert_prewarms_total")
    apw0 = alert_prewarms.value
    exp0 = outcome_total("expired")
    pa = np.asarray([1, 2, 3, 4], np.int32)
    ref_slo = offline.generate(pa[None], n_new=24)[0]
    slo_dir = tempfile.mkdtemp(prefix="chaos_slo_")
    # the queue-phase latency SLO: waits past 0.1s are budget burn —
    # under the storm they appear SECONDS before any 300s deadline
    # could possibly miss, so the alert firing IS the early signal
    slo_eng = AlertEngine(
        [SLOSpec("inter-latency", objective="latency", target=0.9,
                 phase="queue", threshold_s=0.1, window_s=600.0,
                 windows=[(0.4, 1.2, 1.5, "page")])])
    # the burning SLO reads the queue-phase latency series — by now
    # the fleet/disagg/step-load scenarios have been feeding it for
    # minutes, so this top-up is normally a no-op guard; it only
    # sleeps when the preceding scenarios ran implausibly fast
    def _queue_series_span():
        spans = [tsdb.span(k) for k in tsdb.series()
                 if k.startswith("fleet_request_phase_seconds")
                 and 'phase="queue"' in k]
        return max(spans, default=0.0)

    history_by = time.monotonic() + min_history_s + 30.0
    while (_queue_series_span() < min_history_s
           and time.monotonic() < history_by):
        time.sleep(0.25)
    if _queue_series_span() < min_history_s:
        problems.append(
            f"queue-phase series never reached {min_history_s:g}s of "
            f"recorded history (got {_queue_series_span():.1f}s)")
    recorder = telemetry.get_flight_recorder()
    recorder.install_dump(slo_dir, host="chaos", alerts=slo_eng)
    fleet3 = ServingFleet(gpt, n_replicas=1, n_slots=2, max_len=32,
                          block_size=4, tick_batch=1,
                          tick_timeout_s=None)
    # reactive targets deliberately untrippable (30s wait target, no
    # depth ceiling, no forecaster): ONLY the burn-rate alert can
    # drive the scale-up, so the pre-warm attribution is airtight
    pol3 = AutoscalePolicy(min_replicas=1, max_replicas=2,
                           queue_wait_p99_target_s=30.0,
                           up_consecutive=2, down_consecutive=1000,
                           cooldown_s=0.3)
    scaler3 = Autoscaler(fleet3, pol3, interval_s=0.05,
                         alert_engine=slo_eng).start()
    try:
        # enough backlog that the storm outlasts the engine's 1.2s
        # long-window coverage on a fast box (64 requests drained in
        # ~1.2 s on the box of ISSUE 36's session, parent tree and
        # change alike: three times that)
        hs3 = [fleet3.submit_async(pa, n_new=24, tenant="inter",
                                   deadline_s=300.0)
               for _ in range(192)]
        fire_by = time.monotonic() + 120
        while time.monotonic() < fire_by:
            if alert_prewarms.value - apw0 >= 1:
                break
            time.sleep(0.02)
        if alert_prewarms.value - apw0 < 1:
            problems.append(
                "induced overload produced no ALERT-attributed "
                f"pre-warm (alerts: {slo_eng.alerts()})")
        if outcome_total("expired") - exp0 != 0:
            problems.append("an interactive deadline miss preceded "
                            "the burn-rate alert pre-warm")
        if all(h.done() for h in hs3):
            problems.append("storm drained before the kill — no "
                            "in-flight forensics to freeze")
        # SIGKILL the storm's original replica mid-decode,
        # IMMEDIATELY after the pre-warm: the kill freezes the black
        # box while its requests' spans are still open, then
        # everything migrates to the pre-warmed replica
        fleet3.kill(0)
        # the alert's lifecycle must be observable on the AGGREGATED
        # scrape (the engine's families beacon like any other; the
        # transitions counter is monotonic, so the observation is
        # race-free even after the burn resolves)
        telemetry.publish_beacon(slo_dir, "chaos", registry=registry)
        # the aggregated view serves the PROCESS store at /query —
        # the burn the engine decided on must be reproducible from
        # the recorded history over HTTP (ISSUE 16)
        fr3 = telemetry.FleetRegistry(slo_dir, stale_after_s=3600.0,
                                      tsdb=tsdb)
        with telemetry.start_metrics_server(fr3, port=0) as srv3:
            agg_body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv3.port}/metrics",
                timeout=5).read().decode()
            fired = [a for a in slo_eng.alerts()
                     if a["slo"] == "inter-latency"
                     and a.get("t_fired") is not None]
            if not fired:
                problems.append("no fired inter-latency alert to "
                                "check the /query burn window against")
            else:
                wall_fired = time.time() - (time.monotonic()
                                            - fired[0]["t_fired"])
                qdoc = json.loads(urllib.request.urlopen(
                    f"http://127.0.0.1:{srv3.port}/query?"
                    "series=fleet_slo_burn_rate&slo=inter-latency&"
                    f"window=1.2s&start={wall_fired - 5.0}&"
                    f"end={time.time() + 1.0}",
                    timeout=5).read().decode())
                burns = [p[1] for r in qdoc.get("results", ())
                         for p in r.get("points", ())]
                if not burns:
                    problems.append(
                        "/query returned no burn-rate history over "
                        f"the firing window ({qdoc})")
                elif max(burns) < 1.5:
                    problems.append(
                        "/query burn-rate history never reached the "
                        f"1.5 firing threshold (max {max(burns):.3g})"
                        " — inconsistent with the engine's decision")
        for needle in ('fleet_slo_alert_transitions_total'
                       '{slo="inter-latency",to="firing",'
                       'host="chaos"}',
                       'fleet_slo_alert_firing{slo="inter-latency",'
                       'host="chaos"}',
                       'fleet_autoscale_alert_prewarms_total'
                       '{host="chaos"}'):
            if needle not in agg_body:
                problems.append(f"aggregated scrape missing {needle}")
        for i, h in enumerate(hs3):
            try:
                if not np.array_equal(h.result(timeout=300), ref_slo):
                    problems.append(f"slo-storm output {i} mismatch "
                                    "after the kill")
            except Exception as e:
                problems.append(f"slo-storm request {i} failed after "
                                f"the kill: {e}")
    finally:
        scaler3.close()
        fleet3.shutdown(drain=True)
        recorder.uninstall_dump()
    if outcome_total("expired") - exp0 != 0:
        problems.append("interactive deadline misses during the SLO "
                        "kill storm")
    bundles = flightrec.list_bundles(slo_dir)
    if len(bundles) != 1:
        problems.append(f"expected exactly 1 postmortem bundle, "
                        f"found {len(bundles)}")
    else:
        # merged timeline: the victim's final dispatch events, its
        # open spans at the kill, and the alert state — stitched
        # against the beaconed trace store
        telemetry.publish_beacon(
            slo_dir, "chaos", registry=registry,
            trace_events=telemetry.get_tracer().trace_events())
        pm = _load_postmortem()
        bdoc = flightrec.load_bundle(bundles[0])
        entries = pm.merge_timeline(bdoc,
                                    pm.build_trace_store(slo_dir))
        if bdoc.get("reason") != "chaos_kill: replica 0":
            problems.append(f"bundle reason {bdoc.get('reason')!r}")
        if not any(e["src"] == "event" and e["what"] == "dispatch"
                   and "replica=0" in e["detail"] for e in entries):
            problems.append("postmortem timeline lost the victim's "
                            "final dispatch events")
        if not any(e["src"] == "open" for e in entries):
            problems.append("postmortem timeline holds no open spans "
                            "(the in-flight work at the kill)")
        if not any(e["src"] == "alert"
                   and e["what"] == "slo:inter-latency"
                   for e in entries):
            problems.append("postmortem timeline lost the alert "
                            "state")
        if not any(e["src"] == "span" for e in entries):
            problems.append("postmortem timeline stitched no trace-"
                            "store spans")
        # ISSUE 16: the bundle carries the victim's pre-crash metric
        # history, and the burning SLO's underlying series spans the
        # required window into the kill
        hist = (bdoc.get("history") or {}).get("series") or {}
        qspans = [pts[-1][0] - pts[0][0]
                  for k, ent in hist.items()
                  if k.startswith("fleet_request_phase_seconds")
                  and 'phase="queue"' in k
                  for pts in [ent.get("points") or []] if len(pts) > 1]
        # dump_recent keeps the last 300s; the assert floor is the
        # smaller of that and min_history_s, minus sampling slack
        floor = min(min_history_s, 300.0) - 5.0
        if not qspans:
            problems.append("bundle history holds no queue-phase "
                            "series (the burning SLO's source)")
        elif max(qspans) < floor:
            problems.append(
                f"bundle history for the queue-phase series spans "
                f"{max(qspans):.1f}s < {floor:.1f}s pre-crash")
        if not pm.render_history(bdoc):
            problems.append("postmortem render_history produced "
                            "nothing for a bundle with history")
    shutil.rmtree(slo_dir, ignore_errors=True)

    # -- production front door (ISSUE 18): a REAL overload storm, no
    # FaultInjector (the fault-count matrix below stays exact).  An
    # all-bad batch tenant aged past the long burn window drives the
    # engine's admission projection; the attached ladder walks a
    # 2-replica fleet to the shed rung — the batch tenant is REJECTED
    # with a server-advised retry-after, interactive budgets are
    # capped — holds there long enough for the 1s TSDB recorder to
    # witness the elevated rung, then walks back to rung 0 once the
    # burn clears.  Interactive traffic rides straight through with
    # ZERO deadline misses, a near-deadline request races a hedge on
    # the second replica (first completion wins, the loser is always
    # cancelled), and the whole ladder walk is REPLAYED from the
    # recorded history over /query (ISSUE 16). ---------------------
    from deeplearning4j_tpu.serving import (AdmissionRejectedError,
                                            DegradeLadder, TenantQuota)

    dreg = telemetry.MetricsRegistry()
    dfam = dreg.counter("fleet_requests_total",
                        labelnames=("tenant", "outcome"))
    deg_eng = AlertEngine(
        [SLOSpec("smoke-degrade", target=0.9, tenant="bulk",
                 window_s=600.0, windows=[(0.1, 0.3, 1.5, "page")])],
        source=dreg, registry=telemetry.MetricsRegistry())
    deg_eng.evaluate(now=0.0)            # prime the history
    for t in (0.2, 0.4, 0.6):            # 100% bad, past the 0.3s
        dfam.labels(tenant="bulk", outcome="failed").inc(5)
        deg_eng.evaluate(now=t)          # long window: burn 10x
    exp_d0 = outcome_total("expired")
    hlaunch = counter("fleet_hedges_launched_total")
    hcancel = counter("fleet_hedges_cancelled_total")
    hl0, hc0 = hlaunch.value, hcancel.value
    pd_ = np.asarray([2, 3, 5, 7], np.int32)
    ref_deg = offline.generate(pd_[None], n_new=2)[0]
    ref_full = offline.generate(pd_[None], n_new=8)[0]
    wall_deg0 = time.time()
    with ServingFleet(gpt, n_replicas=2, n_slots=2, max_len=32,
                      block_size=4, tick_batch=1, tick_timeout_s=None,
                      hedge_slack_s=60.0,
                      quotas={"bulk": TenantQuota(klass="batch")}
                      ) as dfleet:
        lad = DegradeLadder(dfleet, deg_eng,
                            thresholds=(1.0, 2.0, 3.0, 4.0, 5.0),
                            hold_down_s=0.0)
        dfleet.attach_degrade(lad)
        rung_hi = lad.evaluate(now=0.6)  # real projection read
        if rung_hi < 2:
            problems.append(f"induced 10x burn drove the ladder to "
                            f"rung {rung_hi}, expected >= 2")
        try:
            dfleet.submit_async(np.asarray([1, 2, 3], np.int32), 4,
                                tenant="bulk")
            problems.append("batch tenant admitted during the "
                            "overload storm (shed rung must reject)")
        except AdmissionRejectedError as e:
            if not e.retry_after_s > 0:
                problems.append("shed batch tenant carried no "
                                "retry_after_s hint")
        # the interactive storm rides THROUGH the overload: degraded
        # (n_new capped 8 -> 2, greedy forced) but never rejected and
        # never expired, and the capped outputs stay byte-identical
        # to the offline prefix
        hds = [dfleet.submit_async(pd_, n_new=8, tenant="chat",
                                   deadline_s=300.0)
               for _ in range(6)]
        # hold the rung while the 1s-cadence recorder samples it: the
        # /query replay below reads the RECORDED walk, so at least
        # one beacon tick must witness the elevated rung
        time.sleep(2.2)
        for i, h in enumerate(hds):
            try:
                if not np.array_equal(h.result(timeout=300), ref_deg):
                    problems.append(
                        f"degraded storm output {i} not "
                        "byte-identical to the capped offline prefix")
            except Exception as e:
                problems.append(f"degraded storm request {i} failed "
                                f"during the overload: {e}")
        for i in range(12):              # the burn cleared: walk down
            rung = lad.evaluate(now=10.0 + i)
            if rung == 0:
                break
        if rung != 0:
            problems.append("ladder did not walk back to rung 0 "
                            "after the burn cleared")
        if not np.array_equal(
                dfleet.submit(pd_, n_new=8, tenant="chat",
                              timeout=300), ref_full):
            problems.append("post-recovery request still degraded "
                            "(output not byte-identical to offline)")
        # near-deadline interactive request: the front door hedges it
        # onto the second warm replica — first completion wins, and
        # once the race resolves launched == cancelled exactly
        hh = dfleet.submit_async(pd_, n_new=8, tenant="chat",
                                 deadline_s=30.0)
        if not np.array_equal(hh.result(timeout=300), ref_full):
            problems.append("hedged request output mismatch")
        hedge_by = time.monotonic() + 30
        while time.monotonic() < hedge_by:
            if (hlaunch.value - hl0 >= 1
                    and hcancel.value - hc0 == hlaunch.value - hl0):
                break
            time.sleep(0.01)
        if hlaunch.value - hl0 < 1:
            problems.append("near-deadline request launched no hedge")
        elif hcancel.value - hc0 != hlaunch.value - hl0:
            problems.append(
                "hedge race left unresolved: launched "
                f"{hlaunch.value - hl0} != cancelled "
                f"{hcancel.value - hc0}")
        # let the recorder witness the recovered rung before the
        # replay reads the history
        time.sleep(1.3)
    if outcome_total("expired") - exp_d0 != 0:
        problems.append("interactive deadline misses during the "
                        "overload storm")
    # replay the ladder walk from the RECORDED history over /query:
    # the rung the storm reached and the recovery to 0 must both be
    # reproducible from the wire, not just from in-process state
    deg_dir = tempfile.mkdtemp(prefix="chaos_degrade_")
    telemetry.publish_beacon(deg_dir, "chaos", registry=registry)
    frd = telemetry.FleetRegistry(deg_dir, stale_after_s=3600.0,
                                  tsdb=tsdb)
    with telemetry.start_metrics_server(frd, port=0) as dsrv:
        qdoc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{dsrv.port}/query?"
            f"series=fleet_degrade_rung&start={wall_deg0 - 2.0}&"
            f"end={time.time() + 1.0}", timeout=5).read().decode())
        rungs = [p[1] for r in qdoc.get("results", ())
                 for p in r.get("points", ())]
        if not rungs:
            problems.append("/query returned no fleet_degrade_rung "
                            f"history over the storm window ({qdoc})")
        else:
            if max(rungs) < 2:
                problems.append(
                    "recorded ladder walk never reached rung 2 (max "
                    f"{max(rungs):.0f}) — inconsistent with the shed "
                    "the storm observed")
            if rungs[-1] != 0:
                problems.append(
                    "recorded ladder walk did not return to rung 0 "
                    f"(last sample {rungs[-1]:.0f})")
    shutil.rmtree(deg_dir, ignore_errors=True)

    # -- sanitizer: one deliberate nan trip so the series has a
    # labeled child on the wire (check_finite itself is unconditional
    # — DL4J_TPU_SANITIZE gates the CALL SITES, not the check) -------
    from deeplearning4j_tpu.analysis import SanitizerError, sanitize
    try:
        sanitize.check_finite("chaos/probe", float("nan"))
        problems.append("sanitizer did not trip on NaN")
    except SanitizerError:
        pass

    # -- static analysis: lint series on the wire ----------------------
    ct.emit_analysis_series(problems)
    # the LIVE configuration's lock-order graph (fleet + ladder +
    # autoscaler + alert + tsdb threads) must be acyclic — a CONC301
    # cycle is a latent deadlock and fails the chaos run outright
    ct.assert_live_lock_order(problems, cache_path=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".dl4j_lint_cache.json"))

    # -- every kind fired (preempt thrice: matrix + bit-identical run
    # + pipeline fleet run; every scheduled serve stall throttled a
    # scheduler pass) --
    expected = {k: 1 for k in resilience.FAULT_KINDS}
    expected["preempt"] = 3
    all_serve_plans = (SERVE_CRASH_PLAN + SERVE_STALL_PLAN
                       + SERVE_TP_CRASH_PLAN + SERVE_SPEC_CRASH_PLAN)
    expected["serve_tick_stall"] = sum(
        s.startswith("serve_tick_stall") for s in all_serve_plans)
    expected["serve_tick_fail"] = sum(
        s.startswith("serve_tick_fail") for s in all_serve_plans)
    for k in resilience.FAULT_KINDS:
        delta = fault_counter.labels(kind=k).value - faults_before[k]
        if delta != expected[k]:
            problems.append(f"faults_injected_total{{kind={k}}} grew "
                            f"{delta} != {expected[k]}")

    # -- scrape: the recovery series are on the wire -------------------
    body = ct.scrape_body(telemetry, registry)
    required = list(ct.RESILIENCE_SERIES)
    required += [f'faults_injected_total{{kind="{k}"}}'
                 for k in resilience.FAULT_KINDS]
    required += ["retry_attempts_bucket", "retry_backoff_seconds_bucket"]
    required += ["lint_lock_graph_cycles"]
    # the fleet/salvage counters must carry the REAL recovery values on
    # the wire, not just exist
    for needle in ("fleet_preempt_broadcasts_total",
                   'fleet_resumes_total{outcome="resumed"}',
                   'fleet_elastic_resumes_total{direction="shrink"}',
                   "kv_slots_salvaged_total",
                   # disagg handoff (ISSUE 14): the prefill->decode
                   # block transfer + the decode-side tier restore
                   # must carry real values after the disagg scenario
                   "kv_handoff_blocks_total",
                   "kv_tier_fetches_total",
                   "serve_watchdog_restarts_total",
                   # the step-load scenario's autoscale actions, both
                   # directions, on the wire (ISSUE 12)
                   'fleet_autoscale_actions_total{direction="up"}',
                   'fleet_autoscale_actions_total{direction="down"}',
                   # the predictive pre-warm that beat the reactive
                   # signals to the scale-up (ISSUE 13)
                   "fleet_autoscale_prewarms_total",
                   # the ALERT-attributed pre-warm + the bundle the
                   # SLO kill storm published (ISSUE 15)
                   "fleet_autoscale_alert_prewarms_total",
                   "postmortem_bundles_total"):
        for line in body.splitlines():
            if line.startswith(needle + " "):
                if float(line.rsplit(" ", 1)[1]) <= 0:
                    problems.append(f"{needle} scraped as 0 after "
                                    "recoveries ran")
                break
        else:
            problems.append(f"{needle} missing from the scrape")
    # the fleet migration outcome must carry a REAL value on the wire
    for line in body.splitlines():
        if (line.startswith("fleet_requests_total{")
                and 'outcome="migrated"' in line
                and float(line.rsplit(" ", 1)[1]) > 0):
            break
    else:
        problems.append('fleet_requests_total{outcome="migrated"} '
                        "missing or 0 on the scrape after a replica "
                        "kill")
    # ZERO interactive deadline misses through the 1->2->1 step load:
    # the expired outcome for the interactive tenant must be absent
    # (never minted) or scrape as 0
    for line in body.splitlines():
        if (line.startswith("fleet_requests_total{")
                and 'tenant="inter"' in line
                and 'outcome="expired"' in line
                and float(line.rsplit(" ", 1)[1]) > 0):
            problems.append(
                "interactive tenant missed deadlines during the "
                f"autoscale step load: {line}")
    required += ct.ANALYSIS_SERIES
    # ISSUE 18: the overload storm's admission outcomes, ladder rung,
    # hedge race counters and degrade/hedge flight events on the wire
    required += ct.DEGRADE_SERIES
    required += ['sanitizer_trips_total{mode="nan"}']
    # ISSUE 13: the prediction gauges the step-load scenario drove,
    # and the optimizer-step device-phase samples the pipeline chaos
    # run's ShardedTrainer folded in
    required += [
        'fleet_autoscale_forecast{signal="firing"}',
        'fleet_autoscale_forecast{signal="breach_s"}',
        'fleet_device_phase_seconds_bucket{device="cpu:0",'
        'phase="optimizer_step"',
        # ISSUE 15: the burn-rate alert's lifecycle on the wire, and
        # the flight-recorder events the scenarios fed
        'fleet_slo_alert_transitions_total{slo="inter-latency",'
        'to="firing"}',
        'fleet_slo_alert_firing{slo="inter-latency"}',
        'fleet_slo_error_budget_remaining{slo="inter-latency"}',
        'flight_events_total{kind="dispatch"}',
        # ISSUE 17: the mesh-loss event the tp=2 tick crash recorded
        'flight_events_total{kind="tp_device_loss"}',
        'flight_events_total{kind="chaos_kill"}',
        'flight_events_total{kind="scale"}',
        'flight_events_total{kind="watchdog"}',
    ]
    problems += ct.missing_series(body, required)

    tsdb.close()
    print(json.dumps({"ok": not problems, "problems": problems}))
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
