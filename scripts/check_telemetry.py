#!/usr/bin/env python
"""Telemetry smoke check — the combined train+serve run the acceptance
bar asks for: 5 training iterations + 16 concurrent serve requests with
the Prometheus scrape endpoint live, then assert the scrape is healthy.

Fails (exit 1) when:
* fewer than 20 distinct series are exposed,
* any histogram sum is NaN,
* a required series is missing (``inference_latency_seconds`` buckets,
  ``flash_route_total{path=...}``, the ``mfu`` gauge, the fit loop's
  data-wait/step split, the ``generation_server_*`` serve-decode
  series), or
* the exported span trace or the report embedding is empty.

Runs on CPU inside the tier-1 budget (tiny MLP, seconds) — wired into
``tests/test_telemetry.py::test_check_telemetry_smoke`` un-marked (i.e.
``not slow`` selects it), and runnable standalone:

    JAX_PLATFORMS=cpu python scripts/check_telemetry.py
"""
import json
import math
import os
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

# the mesh-sharded serve smoke needs >= 2 devices; force a virtual CPU
# pair BEFORE jax initializes (no-op in-process under tests/conftest.py,
# which already forces 8)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

# Resilience-layer series that exist in EVERY process that imports the
# training/serving stack (unlabeled families expose at 0) — the plain
# smoke asserts their presence; scripts/chaos_smoke.py additionally
# asserts the labeled/event series after actually firing the faults.
RESILIENCE_SERIES = [
    "train_preemptions_total",
    "train_resumes_total",
    "bad_steps_skipped_total",
    "bad_steps_rolled_back_total",
    "train_lr_backoff_scale",
    "checkpoint_saves_total",
    "checkpoint_failures_total",
    "server_healthy",
    "serve_watchdog_restarts_total",
    "generation_server_tick_failures_total",
    "generation_server_deadline_exceeded_total",
    "generation_server_cancelled_total",
    # zero-downtime fleet layer: coordinated cross-host restart
    # (resilience/coordination.py) and surgical KV salvage
    # (generation_server pool recovery) — chaos_smoke asserts the
    # values after firing real recoveries
    "fleet_preempt_broadcasts_total",
    'fleet_resumes_total{outcome="resumed"}',
    # elastic N->M resume (ISSUE 10): the smoke below saves a world=2
    # checkpoint and fleet-resumes it at world=1, so the shrink
    # counter, world gauge and rendezvous-wait histogram carry live
    # values over the real scrape
    'fleet_elastic_resumes_total{direction="shrink"}',
    "fleet_world_size",
    "fleet_rendezvous_wait_seconds_bucket",
    "kv_slots_salvaged_total",
    "kv_slots_dropped_total",
    # paged-KV layer: block-granular salvage counters (the slot pair
    # above stays for request-level accounting)
    "kv_blocks_salvaged_total",
    "kv_blocks_dropped_total",
]

# Paged KV pool + prefix cache series (PR 7): the smoke below runs two
# same-prompt requests through a small-block server and asserts >= 1
# real prefix hit, so hits/shared carry live values on the wire.
PAGED_KV_SERIES = [
    "kv_blocks_allocated_total",
    "kv_blocks_freed_total",
    "kv_blocks_shared_total",
    "kv_pool_blocks_free",
    "prefix_cache_hits_total",
    "prefix_cache_misses_total",
    'paged_route_total{path="reference"}',
]

# Tiered-KV series (ISSUE 14): the smoke below drives two same-prefix
# requests through a tier-sized-down pool — the interleaved distinct
# prompt EVICTS the first's cached blocks (>= 1 real spill to host
# RAM), and the re-admission restores them (>= 1 tier fetch, one
# batched H2D) with the output byte-identical to the cold decode.
# The handoff pair (export_prefix -> import_blocks into a second
# server) puts real values on the kv_handoff_* counters.
TIERED_KV_SERIES = [
    # kv_pool_blocks_free itself stays in PAGED_KV_SERIES; this list
    # adds the ISSUE 14 gauge-split + tier + handoff families
    "kv_pool_blocks_evictable",
    "kv_host_tier_blocks",
    "kv_tier_spills_total",
    "kv_tier_fetches_total",
    "kv_tier_hits_total",
    "kv_tier_evictions_total",
    "kv_handoff_blocks_total",
    "kv_handoff_bytes_total",
]

# Speculative-decode series (PR 11 + ISSUE 20): the smoke below
# decodes through a draft-verified server (full-depth self-draft ->
# acceptance is exactly 1.0), so proposed/accepted and the
# acceptance-rate gauge carry live values on the wire — and the
# output is byte-compared against the non-speculative decode of the
# same prompt.  A second, SAMPLED adaptive-K server (tenant-tagged
# request) puts the adaptive-depth gauge and the per-tenant
# acceptance series on the scrape too.
SPEC_SERIES = [
    "generation_server_spec_proposed_total",
    "generation_server_spec_accepted_total",
    "generation_server_spec_acceptance_rate",
    'generation_server_scan_ticks_total{k="spec',
    "generation_server_spec_adaptive_k",
    'generation_server_tenant_spec_acceptance_rate'
    '{tenant="spec-tenant"}',
]

# Serving-fleet series (PR 9): the smoke below routes a 2-tenant
# workload through a 2-replica ServingFleet — the repeated hot-tenant
# prompt rides affinity to the warm replica (a real prefix hit there),
# so the admission/dispatch series carry live values on the wire.
FLEET_SERIES = [
    'fleet_requests_total{tenant="hot",outcome="admitted"}',
    'fleet_requests_total{tenant="cold",outcome="admitted"}',
    'fleet_replica_dispatch_total{replica="0",reason="least_loaded"}',
    'fleet_replica_dispatch_total{replica="0",reason="affinity"}',
    "fleet_queue_wait_seconds_bucket",
    "fleet_replicas_healthy",
    "fleet_queue_depth",
    # request-phase decomposition (ISSUE 12): the fleet smoke's
    # requests record per-phase spans, so every phase series carries
    # live values; the deadline'd request feeds the EDF-slack family
    'fleet_request_phase_seconds_bucket{phase="admission"',
    'fleet_request_phase_seconds_bucket{phase="placement"',
    'fleet_request_phase_seconds_bucket{phase="queue"',
    'fleet_request_phase_seconds_bucket{phase="prefill"',
    'fleet_request_phase_seconds_bucket{phase="decode"',
    'fleet_request_phase_seconds_bucket{phase="total"',
    'fleet_edf_slack_seconds_bucket{tenant="hot"',
]

# Fleet observability plane (ISSUE 12): asserted over the AGGREGATED
# 2-worker scrape (this process + a synthetic peer, both published as
# beacons and merged by FleetRegistry) — every entry must appear
# host-tagged AND rolled up.  ISSUE 13 widens the allowlist: the
# continuous device-phase profile must arrive host-tagged from BOTH
# workers with a fleet rollup, and the trace-store gauges must be on
# the same scrape.
FLEET_OBS_SERIES = [
    'generation_server_retired_total{host="workerA"}',
    'generation_server_retired_total{host="workerB"}',
    'generation_server_retired_total{host="fleet"}',
    'fleet_request_phase_seconds_bucket{phase="decode",host="fleet"',
    'fleet_requests_total{tenant="hot",outcome="admitted",host="fleet"}',
    'fleet_host_up{host="workerA"} 1.0',
    'fleet_host_up{host="workerB"} 1.0',
    "fleet_hosts_live 2.0",
    'fleet_beacon_publishes_total{host="workerA"}',
    # per-device continuous profiling (ISSUE 13): the real worker's
    # decode/prefill/verify samples + the synthetic peer's, each
    # host-tagged, plus the fleet rollup of the family
    'fleet_device_phase_seconds_count{device="cpu:0",'
    'phase="decode_tick",host="workerA"}',
    'fleet_device_phase_seconds_count{device="cpu:0",'
    'phase="prefill",host="workerA"}',
    'fleet_device_phase_seconds_count{device="cpu:0",'
    'phase="verify",host="workerA"}',
    'fleet_device_phase_seconds_count{device="cpu:0",'
    'phase="decode_tick",host="workerB"}',
    'fleet_device_phase_seconds_count{device="cpu:0",'
    'phase="decode_tick",host="fleet"}',
    # the on-demand XProf capture summary beacons fleet-wide (the raw
    # trace stays a host-local artifact)
    'fleet_xprof_captures_total{host="workerA"}',
    # cross-worker trace store: the aggregator's own gauges
    "fleet_trace_store_traces",
    "fleet_trace_store_spans",
    "fleet_trace_store_rooted",
]

# SLO error-budget engine (ISSUE 15): the induced-burn smoke below
# drives a synthetic outcome stream through a REAL AlertEngine
# attached to a FleetRegistry and scrapes the aggregated endpoint —
# the alert is observed FIRING on the wire (gauge 1.0 + the
# transitions counter), then RESOLVING once the bleeding stops.
# Asserted against the mid-burn FLEET scrape body, not the process
# registry (the engine exports into the aggregated view).
SLO_SERIES = [
    'fleet_slo_burn_rate{slo="smoke-avail",window="0.1s",'
    'host="fleet"}',
    'fleet_slo_burn_rate{slo="smoke-avail",window="0.3s",'
    'host="fleet"}',
    'fleet_slo_error_budget_remaining{slo="smoke-avail",'
    'host="fleet"}',
    'fleet_slo_alert_state{slo="smoke-avail",host="fleet"}',
    'fleet_slo_alert_firing{slo="smoke-avail",host="fleet"} 1.0',
    'fleet_slo_alert_transitions_total{slo="smoke-avail",'
    'to="firing",host="fleet"} 1',
]

# Production front door (ISSUE 18): the smoke below induces a REAL
# overload (100%-bad tenant traffic aged past the long burn window
# through a real AlertEngine), lets the attached DegradeLadder walk a
# real fleet up to rung 5 (admissions shaped, the batch class shed
# with a typed retry-after) and back to 0, and races one deadline'd
# request's hedge on the second replica — so the admission outcome
# counters, the rung gauge, the hedge race counters and the
# degrade-step flight events all carry live values on the wire.
DEGRADE_SERIES = [
    'fleet_admission_admitted_total{tenant="chat"}',
    'fleet_admission_degraded_total{tenant="chat"}',
    'fleet_admission_rejected_total{tenant="bulk"}',
    "fleet_degrade_rung",
    "fleet_hedges_launched_total",
    "fleet_hedges_won_total",
    "fleet_hedges_cancelled_total",
    'flight_events_total{kind="degrade_step"}',
    'flight_events_total{kind="hedge"}',
]

# Mesh-sharded serving (ISSUE 17): the smoke below decodes one prompt
# through a tp=2 replica spanning two virtual devices — byte-compared
# against the single-chip server — and constructs a mixed fleet, so
# the slice gauge, the tp-degree gauge, the forced reference_tp
# attention route and the PER-DEVICE phase attribution (one decode
# tick folds into EVERY chip of the slice) all carry live values.
MESH_SERIES = [
    'fleet_replica_devices{replica="0"} 1.0',
    'fleet_replica_devices{replica="1"} 2.0',
    "generation_server_tp_degree 2.0",
    'paged_route_total{path="reference_tp"}',
    'fleet_device_phase_seconds_count{device="cpu:1",'
    'phase="decode_tick"}',
]

# Flight recorder (ISSUE 15): the serve smokes above feed the
# process-default ring (admit/retire events), and the SLO section
# writes one explicit postmortem bundle — both families carry live
# values on the MAIN scrape.
FLIGHT_SERIES = [
    'flight_events_total{kind="admit"}',
    'flight_events_total{kind="retire"}',
    "postmortem_bundles_total",
]

# Embedded TSDB (ISSUE 16): every FleetRegistry records its view into
# its store per scrape, so the store's own accounting rides the
# AGGREGATED scrape (the SLO section's fleet endpoint asserts these).
TSDB_SERIES = [
    "fleet_tsdb_series",
    "fleet_tsdb_samples_total",
    "fleet_tsdb_evicted_total",
]

# Predictive-autoscaling series (ISSUE 13): the forecaster below runs
# a synthetic backlog ramp through the REAL fit/publish path, so the
# prediction gauges carry live values; chaos_smoke asserts the
# end-to-end pre-warm against a real ramp.
FORECAST_SERIES = [
    'fleet_autoscale_forecast{signal="slope"}',
    'fleet_autoscale_forecast{signal="backlog"}',
    'fleet_autoscale_forecast{signal="breach_s"}',
    "fleet_autoscale_prewarms_total",
]

#: one complete cross-component request trace must carry all of these
TRACE_PHASES = {"request", "request/admission", "request/placement",
                "request/replica_queue", "request/prefill",
                "request/decode"}

# Static-analysis subsystem series: the lint counter gets labeled
# children from emit_analysis_series() below, which also runs a real
# (small) package-index build so the whole-package-mode series carry
# live values; sanitizer_trips_total is registered by importing the
# training stack (its HELP/TYPE lines are always on the wire;
# chaos_smoke additionally fires a real trip).
ANALYSIS_SERIES = [
    'lint_findings_total{rule="JIT101",severity="error"}',
    "sanitizer_trips_total",
    "lint_modules_indexed_total",
    "lint_runtime_seconds_bucket",
]

# one deliberate trace-safety violation — linting it populates
# lint_findings_total{rule=,severity=} without walking the whole tree
ANALYSIS_FIXTURE = (
    "import time\n"
    "import jax\n"
    "@jax.jit\n"
    "def f(x):\n"
    "    t = time.time()\n"
    "    return x * t\n")


def emit_analysis_series(problems) -> None:
    """Lint the known-bad fixture and count the findings into the
    process registry (the CLI's --telemetry hook, in-process) — shared
    with chaos_smoke so both reports cover the analysis subsystem.
    Also builds a real (small) package index over the analysis
    subpackage itself so the whole-package-mode series
    (lint_modules_indexed_total / lint_runtime_seconds) carry live
    values on the wire."""
    from deeplearning4j_tpu.analysis import jit_lint, package_index
    from deeplearning4j_tpu.analysis.cli import emit_telemetry
    findings = jit_lint.lint_source(ANALYSIS_FIXTURE, "<fixture>")
    if not any(f.rule == "JIT101" for f in findings):
        problems.append(
            "analysis fixture produced no JIT101 finding "
            f"(got {[f.rule for f in findings]})")
    emit_telemetry(findings)
    pkg = os.path.join(os.path.dirname(package_index.__file__))
    _, _, stats = package_index.build_index(pkg, root=os.path.dirname(
        os.path.dirname(pkg)))
    if stats.modules < 5:
        problems.append(
            f"package index over analysis/ saw {stats.modules} modules")
    package_index.emit_index_telemetry(stats)


def assert_live_lock_order(problems, cache_path=None) -> None:
    """Build the lock-order graph of the LIVE serving configuration —
    the fleet scheduler, degrade-ladder clock, autoscaler, alert
    engine and TSDB recorder threads all live under ``serving/`` +
    ``telemetry/`` — and assert it is ACYCLIC (ISSUE 19): a CONC301
    cycle there is a latent production deadlock, so the chaos run
    fails on it rather than leaving it to the lint gate.  The pass
    runtime lands in ``lint_runtime_seconds`` and the cycle count on
    the ``lint_lock_graph_cycles`` gauge so the scrape proves the
    probe ran."""
    import time as _time
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.analysis import lock_order, package_index
    pkgroot = os.path.dirname(os.path.dirname(package_index.__file__))
    root = os.path.dirname(pkgroot)
    t0 = _time.perf_counter()
    merged, stats = {}, package_index.IndexStats()
    for sub in ("serving", "telemetry"):
        idx, _, st = package_index.build_index(
            os.path.join(pkgroot, sub), root=root,
            cache_path=cache_path, run_local_passes=False)
        merged.update(idx.modules)
        stats.modules += st.modules
        stats.cache_hits += st.cache_hits
    live = package_index.PackageIndex(merged)
    cycles = [f for f in lock_order.lint_package(live)
              if f.rule == "CONC301"]
    stats.elapsed_s = _time.perf_counter() - t0
    for f in cycles:
        problems.append(
            f"lock-order CYCLE in the live serving config: {f.message}")
    if stats.modules < 10:
        problems.append("live lock-order probe indexed only "
                        f"{stats.modules} modules")
    telemetry.gauge(
        "lint_lock_graph_cycles",
        "CONC301 cycles in the live serving configuration's "
        "lock-order graph (must be 0)").set(len(cycles))
    package_index.emit_index_telemetry(stats)


def scrape_body(telemetry, registry) -> str:
    """Serve one scrape over a real HTTP endpoint and return the
    Prometheus text body (shared with chaos_smoke)."""
    with telemetry.start_metrics_server(registry, port=0) as srv:
        return urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5
        ).read().decode()


def missing_series(body: str, required) -> list:
    return [f"required series missing: {needle!r}"
            for needle in required if needle not in body]


def main() -> int:
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration, telemetry)
    from deeplearning4j_tpu import kernels
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterator import ListDataSetIterator
    from deeplearning4j_tpu.nn.conf.layers_core import (DenseLayer,
                                                        OutputLayer)
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.parallel import ParallelInference
    from deeplearning4j_tpu.ui import InMemoryStatsStorage, render_report

    import jax.numpy as jnp

    registry = telemetry.get_registry()
    tracer = telemetry.get_tracer()
    problems = []

    # -- train: 5 iterations with the telemetry listener ---------------
    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Adam(learning_rate=1e-2)).list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=4, activation="softmax",
                               loss="mcxent"))
            .build())
    model = MultiLayerNetwork(conf).init()
    storage = InMemoryStatsStorage()
    # ~2*params*3 train FLOPs/example for the 8-16-4 MLP — real enough
    # for the mfu gauge to be a number, which is all a smoke asserts
    flops = 2 * 3 * (8 * 16 + 16 * 4)
    model.set_listeners(telemetry.TelemetryListener(
        storage=storage, flops_per_example=flops, peak_flops=1e12))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5 * 32, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, len(x))]
    model.fit(ListDataSetIterator(DataSet(x, y).batch_by(32)), n_epochs=1)

    # -- touch the kernel router so flash_route_total has a child ------
    q = jnp.asarray(rng.normal(size=(1, 2, 8, 4)), jnp.float32)
    kernels.attention(q, q, q)

    # -- serve: 16 concurrent requests ---------------------------------
    # the registry is process-global (tests may have served already):
    # assert the DELTA this run contributes
    lat = registry.histogram("inference_latency_seconds")
    lat_before = lat.count
    xs = [rng.normal(size=(8,)).astype(np.float32) for _ in range(16)]
    with ParallelInference(model, batch_limit=8, timeout_ms=5) as pi:
        errs = []

        def call(i):
            try:
                pi.output(xs[i])
            except Exception as e:  # pragma: no cover - smoke surface
                errs.append(f"request {i}: {e}")

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        problems += errs

    # -- serve decode: 3 requests through 2 slots (exercises the
    # continuous-batching queue) -------------------------------------
    from deeplearning4j_tpu.parallel import GenerationServer
    from deeplearning4j_tpu.zoo.gpt import Gpt

    retired = registry.counter("generation_server_retired_total")
    syncs = registry.counter("generation_server_host_syncs_total")
    retired_before = retired.value
    gpt = Gpt(vocab_size=50, max_len=32, d_model=32, n_layers=2,
              n_heads=4, d_ff=64, seq_len=8, compute_dtype=None,
              seed=3).init_graph()
    with GenerationServer(gpt, n_slots=2, max_len=32) as gs:
        gh = [gs.submit_async(np.asarray([1, 2, 3, 4], np.int32),
                              n_new=4) for _ in range(3)]
        for i, handle in enumerate(gh):
            try:
                out = handle.result(timeout=300)
                if out.shape != (8,):
                    problems.append(
                        f"generation request {i}: shape {out.shape}")
            except Exception as e:  # pragma: no cover - smoke surface
                problems.append(f"generation request {i}: {e}")
        # one solo request with an empty queue: the scheduler must
        # fuse its 4 ticks into ONE lax.scan dispatch (k=4) and poll
        # the host once for it.  The on-demand XProf trigger is armed
        # around it: the next measured dispatch runs under a REAL
        # jax.profiler capture whose summary lands on the registry
        # (and so on every beacon) while the raw trace stays local.
        prof = telemetry.get_profiler()
        xprof_captures = registry.counter("fleet_xprof_captures_total")
        xc0 = xprof_captures.value
        syncs_before = syncs.value
        with tempfile.TemporaryDirectory() as xprof_dir:
            prof.request_xprof(xprof_dir, dispatches=1)
            try:
                gs.submit(np.asarray([4, 3, 2, 1], np.int32), n_new=4,
                          timeout=300)
            except Exception as e:  # pragma: no cover - smoke surface
                problems.append(f"solo scan request: {e}")
        if syncs.value - syncs_before != 1:
            problems.append(
                f"solo 4-token request cost {syncs.value - syncs_before}"
                " host syncs (expected 1 fused k=4 scan)")
        if xprof_captures.value - xc0 != 1:
            problems.append("on-demand XProf trigger did not complete "
                            "exactly one capture")
        if registry.gauge("fleet_xprof_capture_files").value < 1:
            problems.append("XProf capture summary reports no files "
                            "written")
    if retired.value - retired_before != 4:
        problems.append(f"generation_server_retired_total grew "
                        f"{retired.value - retired_before} != 4")
    # the scheduler's scoped spans: a tick holds its dispatch call and
    # its one read (ISSUE 32), so a device gap is named by either
    span_names = {ev["name"] for ev in tracer.events()}
    for span in ("serve/admit", "serve/tick", "serve/launch",
                 "serve/poll", "serve/retire"):
        if span not in span_names:
            problems.append(f"no {span} span recorded by the scheduler")

    # -- paged KV: two requests sharing one system prompt must score a
    # real prefix-cache hit (the second prefills only its suffix) ----
    hits = registry.counter("prefix_cache_hits_total")
    shared = registry.counter("kv_blocks_shared_total")
    hits_before, shared_before = hits.value, shared.value
    sys_prompt = np.asarray([3, 1, 4, 1, 5, 9, 2, 6, 5], np.int32)
    with GenerationServer(gpt, n_slots=2, max_len=32,
                          block_size=4) as gs2:
        out_a = gs2.submit(sys_prompt, n_new=4, timeout=300)
        out_b = gs2.submit(sys_prompt, n_new=4, timeout=300)
    if hits.value - hits_before < 1:
        problems.append("two same-system-prompt requests produced no "
                        "prefix_cache_hits_total increment")
    if shared.value - shared_before < 1:
        problems.append("prefix hit mapped no shared blocks "
                        "(kv_blocks_shared_total flat)")
    if not np.array_equal(out_a, out_b):
        problems.append("prefix-hit decode diverged from the cold "
                        "decode of the same prompt")

    # -- tiered KV: a tier-backed server whose pool is too small for
    # two working sets — the second distinct prompt EVICTS the first's
    # cached blocks (spill to host RAM), the first's re-admission
    # restores them with one batched H2D (tier fetch), outputs
    # identical; then the prefix hands off to a SECOND server
    # (export -> import) whose admission tier-fetches it ------------
    t_spills = registry.counter("kv_tier_spills_total")
    t_fetches = registry.counter("kv_tier_fetches_total")
    t_handoff = registry.counter("kv_handoff_blocks_total")
    ts0, tf0, th0 = t_spills.value, t_fetches.value, t_handoff.value
    tp_a = np.asarray([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9],
                      np.int32)
    tp_b = np.asarray([2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4, 5, 9],
                      np.int32)
    with GenerationServer(gpt, n_slots=2, max_len=32, block_size=4,
                          kv_blocks=8, host_tier_blocks=8,
                          tick_timeout_s=None) as gt:
        tier_a = gt.submit(tp_a, n_new=12, timeout=300)
        gt.submit(tp_b, n_new=12, timeout=300)     # evicts A -> spill
        if t_spills.value - ts0 < 1:
            problems.append("tier-sized-down pool produced no "
                            "kv_tier_spills_total increment")
        tier_a2 = gt.submit(tp_a, n_new=12, timeout=300)  # tier fetch
        if t_fetches.value - tf0 < 1:
            problems.append("re-admission of the spilled prefix "
                            "produced no kv_tier_fetches_total "
                            "increment")
        if not np.array_equal(tier_a, tier_a2):
            problems.append("tier-fetch decode diverged from the cold "
                            "decode of the same prompt")
        handoff_payload = gt.export_prefix(tp_a)
    if len(handoff_payload) != 3:
        problems.append(f"export_prefix returned "
                        f"{len(handoff_payload)} blocks, expected 3")
    with GenerationServer(gpt, n_slots=2, max_len=32, block_size=4,
                          tick_timeout_s=None) as gi:
        gi.import_blocks(handoff_payload)
        tier_a3 = gi.submit(tp_a, n_new=12, timeout=300)
        if not np.array_equal(tier_a, tier_a3):
            problems.append("handed-off decode diverged from the "
                            "origin server's decode")
        if gi.stats()["tier_fetches"] < 1:
            problems.append("handoff admission restored no tier "
                            "blocks on the importing server")
    if t_handoff.value - th0 != 3:
        problems.append("kv_handoff_blocks_total grew "
                        f"{t_handoff.value - th0} != 3")

    # -- speculative decode: a draft-verified server must agree with
    # the plain server byte-for-byte AND count real proposals -------
    spec_prop = registry.counter(
        "generation_server_spec_proposed_total")
    spec_acc = registry.counter(
        "generation_server_spec_accepted_total")
    sp0, sa0 = spec_prop.value, spec_acc.value
    spec_prompt = np.asarray([2, 7, 1, 8, 2, 8], np.int32)
    with GenerationServer(gpt, n_slots=2, max_len=32,
                          tick_timeout_s=None) as gp:
        ref_out = gp.submit(spec_prompt, n_new=6, timeout=300)
    with GenerationServer(gpt, n_slots=2, max_len=32,
                          tick_timeout_s=None,
                          speculative={"k": 2, "rounds": 2,
                                       "draft_layers": 2}) as gs3:
        spec_out = gs3.submit(spec_prompt, n_new=6, timeout=300)
        spec_stats = gs3.stats()
    if not np.array_equal(spec_out, ref_out):
        problems.append("speculative decode diverged from the "
                        "non-speculative decode of the same prompt")
    if spec_prop.value - sp0 < 1:
        problems.append("speculative decode proposed no draft tokens "
                        "(generation_server_spec_proposed_total flat)")
    if spec_acc.value - sa0 != spec_prop.value - sp0:
        problems.append(
            "full-depth self-draft must accept every proposal "
            f"(accepted {spec_acc.value - sa0} != proposed "
            f"{spec_prop.value - sp0})")
    if spec_stats["spec_acceptance_rate"] != 1.0:
        problems.append("per-instance spec acceptance rate "
                        f"{spec_stats['spec_acceptance_rate']} != 1.0")

    # -- sampled speculative decode + adaptive K (ISSUE 20): a
    # tenant-tagged SAMPLED request through an adaptive-depth server
    # puts the adaptive-K gauge and the per-tenant acceptance series
    # on the scrape with real post-dispatch values ------------------
    adaptive_k = registry.gauge("generation_server_spec_adaptive_k")
    with GenerationServer(gpt, n_slots=2, max_len=32,
                          tick_timeout_s=None,
                          speculative={"k": 2, "rounds": 2,
                                       "draft_layers": 2,
                                       "adaptive": True,
                                       "k_max": 3}) as ga:
        samp_out = ga.submit(spec_prompt, n_new=6, sampling={
            "temperature": 0.8, "top_k": 8, "seed": 5},
            tenant="spec-tenant", timeout=300)
        ctl_snap = ga._spec_ctl.snapshot()
    if samp_out.shape != (12,) or not (
            (samp_out >= 0).all() and (samp_out < 50).all()):
        problems.append("sampled speculative decode returned a "
                        f"malformed stream (shape {samp_out.shape})")
    if not 1 <= adaptive_k.value <= 3:
        problems.append("generation_server_spec_adaptive_k "
                        f"{adaptive_k.value} outside [1, k_max=3]")
    if ctl_snap["global_proposed"] < 1:
        problems.append("acceptance controller observed no "
                        "proposals from the sampled spec decode")
    tenant_rate = registry.gauge(
        "generation_server_tenant_spec_acceptance_rate",
        labelnames=("tenant",)).labels(tenant="spec-tenant")
    if not 0.0 <= tenant_rate.value <= 1.0:
        problems.append("per-tenant spec acceptance rate "
                        f"{tenant_rate.value} outside [0, 1]")

    # -- serving fleet: 2 replicas x 2 tenants through the admission
    # router — the repeated hot-tenant prompt must ride affinity to
    # the warm replica and score a real prefix hit THERE -------------
    from deeplearning4j_tpu.serving import ServingFleet

    with ServingFleet(gpt, n_replicas=2, n_slots=2, max_len=32,
                      block_size=4, tick_batch=1,
                      tick_timeout_s=None) as fleet:
        fp = np.asarray([2, 7, 1, 8, 2, 8, 1, 8, 2], np.int32)
        out_hot = fleet.submit(fp, n_new=4, tenant="hot", timeout=300)
        # deadline'd so the EDF-slack histogram records at dispatch
        fh = fleet.submit_async(fp, n_new=4, tenant="hot",
                                deadline_s=300.0)
        out_rep = fh.result(timeout=300)
        out_cold = fleet.submit(np.asarray([6, 5, 4, 3], np.int32),
                                n_new=4, tenant="cold", timeout=300)
        if out_cold.shape != (8,):
            problems.append(
                f"fleet cold-tenant request: shape {out_cold.shape}")
        if not np.array_equal(out_hot, out_rep):
            problems.append("fleet repeat decode diverged from its "
                            "first decode of the same prompt")
        if fh.replica is None or \
                fleet.replica(fh.replica).stats()["prefix_hits"] < 1:
            problems.append("fleet affinity repeat scored no prefix "
                            "hit on the warm replica")
        if fleet.stats()["healthy_replicas"] != 2:
            problems.append("fleet not fully healthy after the smoke")
        fleet_trace_id = fh.trace_id

    # -- request-scoped tracing: the deadline'd request must have ONE
    # complete cross-component trace (submit -> retire, every phase
    # span stamped with its fleet-minted trace id) ------------------
    tr_names = {e["name"]
                for e in tracer.events_for_trace(fleet_trace_id)}
    if not TRACE_PHASES <= tr_names:
        problems.append(
            f"request trace {fleet_trace_id} incomplete: missing "
            f"{sorted(TRACE_PHASES - tr_names)}")
    if tracer.open_spans():
        problems.append(
            "tracked spans left open after every request retired: "
            f"{[s.name for s in tracer.open_spans()]}")

    # -- production front door (ISSUE 18): induce a REAL overload —
    # all-bad tenant traffic aged past the long burn window drives
    # the engine's admission projection, the attached ladder walks a
    # real 2-replica fleet to rung 5 (budgets capped, batch shed with
    # retry-after) and back down once the burn clears, and a
    # deadline'd request under hedge_slack_s races a hedge ---------
    from deeplearning4j_tpu.serving import (AdmissionRejectedError,
                                            DegradeLadder, TenantQuota)
    from deeplearning4j_tpu.telemetry.slo import AlertEngine, SLOSpec
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
    hlaunch = registry.counter("fleet_hedges_launched_total")
    hcancel = registry.counter("fleet_hedges_cancelled_total")
    hl0, hc0 = hlaunch.value, hcancel.value
    with ServingFleet(gpt, n_replicas=2, n_slots=2, max_len=32,
                      block_size=4, tick_batch=1, tick_timeout_s=None,
                      hedge_slack_s=60.0,
                      quotas={"bulk": TenantQuota(klass="batch")}
                      ) as dfleet:
        lad = DegradeLadder(dfleet, deg_eng,
                            thresholds=(1.0, 2.0, 3.0, 4.0, 5.0),
                            hold_down_s=0.0)
        dfleet.attach_degrade(lad)
        rung = lad.evaluate(now=0.6)     # real projection read
        if rung != 5:
            problems.append(f"induced 10x burn drove the ladder to "
                            f"rung {rung}, expected 5")
        try:
            dfleet.submit_async(np.asarray([1, 2, 3], np.int32), 4,
                                tenant="bulk")
            problems.append("batch tenant admitted during the "
                            "overload (rung 5 must shed)")
        except AdmissionRejectedError as e:
            if not e.retry_after_s > 0:
                problems.append("shed batch tenant carried no "
                                "retry_after_s hint")
        deg_out = dfleet.submit(np.asarray([5, 6, 7], np.int32), 8,
                                tenant="chat", timeout=300)
        if deg_out.shape != (5,):        # n_new 8 -> capped 2
            problems.append(f"rung 5 did not cap n_new: shape "
                            f"{deg_out.shape}, expected (5,)")
        for i in range(12):              # the burn cleared: walk down
            rung = lad.evaluate(now=10.0 + i)
            if rung == 0:
                break
        if rung != 0:
            problems.append("ladder did not walk back to rung 0 "
                            "after the burn cleared")
        full_out = dfleet.submit(np.asarray([5, 6, 7], np.int32), 8,
                                 tenant="chat", timeout=300)
        if full_out.shape != (11,):
            problems.append("post-recovery request still degraded: "
                            f"shape {full_out.shape}, expected (11,)")
        hh = dfleet.submit_async(np.asarray([1, 2, 3, 4], np.int32),
                                 8, tenant="chat", deadline_s=30.0)
        hh.result(timeout=300)
        hedge_deadline = time.monotonic() + 30
        while time.monotonic() < hedge_deadline:
            if (hlaunch.value - hl0 >= 1
                    and hcancel.value - hc0 == hlaunch.value - hl0):
                break
            time.sleep(0.01)
        if hlaunch.value - hl0 < 1:
            problems.append("deadline'd request under hedge_slack_s "
                            "launched no hedge")
        elif hcancel.value - hc0 != hlaunch.value - hl0:
            problems.append(
                "hedge race left unresolved: launched "
                f"{hlaunch.value - hl0} != cancelled "
                f"{hcancel.value - hc0}")

    # -- predictive autoscaling: a synthetic backlog ramp through the
    # REAL forecaster fit/publish path — the prediction gauges carry
    # live values on the scrape, and the math is checked against the
    # known ramp (backlog = 2t, threshold 20, at t=5 -> breach in 5s)
    from deeplearning4j_tpu.serving import BacklogForecaster
    fc = BacklogForecaster(window_s=60.0, min_points=4)
    for t in range(6):
        fc.observe(float(t), 2.0 * t)
    breach = fc.breach_s(20.0)
    if breach is None or abs(breach - 5.0) > 1e-6:
        problems.append(f"forecast on the synthetic ramp predicted "
                        f"{breach}s to breach, expected 5.0s")
    # the prewarm counter exists on every process that imports the
    # autoscaler (unlabeled counter exposes at 0; chaos_smoke asserts
    # the live pre-warm)
    registry.counter("fleet_autoscale_prewarms_total")

    # -- fleet observability plane: TWO workers' beacons aggregate
    # into ONE scrape with {host=} tags and fleet rollups; the same
    # beacons carry closed request spans the aggregator's trace store
    # stitches into ONE submit -> retire tree per request ------------
    worker_b = telemetry.MetricsRegistry()
    worker_b.counter("generation_server_retired_total").inc(2)
    worker_b.counter("fleet_requests_total",
                     labelnames=("tenant", "outcome")).labels(
                         tenant="hot", outcome="admitted").inc(3)
    worker_b.histogram("fleet_device_phase_seconds",
                       labelnames=("device", "phase")).labels(
                           device="cpu:0",
                           phase="decode_tick").observe(0.003)
    with tempfile.TemporaryDirectory() as d:
        with telemetry.MetricsBeacon(d, host="workerA",
                                     interval_s=60.0):
            pass                 # start + final publish
        telemetry.publish_beacon(d, "workerB", registry=worker_b)
        fleet_view = telemetry.FleetRegistry(d, stale_after_s=3600.0)
        with telemetry.start_metrics_server(fleet_view, port=0) as srv:
            obs_body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5
            ).read().decode()
            tr_body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/traces?id="
                f"{fleet_trace_id}", timeout=5).read().decode()
    problems += missing_series(obs_body, FLEET_OBS_SERIES)
    tree = json.loads(tr_body)
    if not tree.get("root") or tree["root"]["name"] != "request":
        problems.append("fleet trace store has no stitched root for "
                        f"trace {fleet_trace_id}")
    else:
        def _names(node):
            out = {node["name"]}
            for c in node["children"]:
                out |= _names(c)
            return out
        got = _names(tree["root"])
        if not {"request/admission", "request/prefill",
                "request/decode"} <= got:
            problems.append(
                f"stitched fleet trace missing phases: {sorted(got)}")
        if tree["orphans"]:
            problems.append("stitched fleet trace left orphan "
                            f"fragments: {tree['orphans']}")
    retired_roll = retired.value + 2
    for line in obs_body.splitlines():
        if line.startswith('generation_server_retired_total'
                           '{host="fleet"} '):
            if float(line.rsplit(" ", 1)[1]) != retired_roll:
                problems.append(
                    "fleet rollup retired_total "
                    f"{line.rsplit(' ', 1)[1]} != sum of workers "
                    f"{retired_roll}")
            break

    # -- elastic fleet resume: a checkpoint recorded at world=2 is
    # fleet-resumed at world=1, so the shrink counter, world gauge and
    # rendezvous-wait histogram carry REAL values on the scrape ------
    from deeplearning4j_tpu.parallel import CheckpointListener
    from deeplearning4j_tpu.resilience import fleet_resume_fit

    elastic = registry.counter("fleet_elastic_resumes_total",
                               labelnames=("direction",))
    shrink0 = elastic.labels(direction="shrink").value
    with tempfile.TemporaryDirectory() as d:
        em = MultiLayerNetwork(conf).init()
        ck = CheckpointListener(os.path.join(d, "ck"),
                                save_every_n_iterations=2,
                                async_save=False, world=2)
        em.set_listeners(ck)
        em.fit(ListDataSetIterator(DataSet(x, y).batch_by(32)),
               n_epochs=1, async_prefetch=False)
        fleet_resume_fit(
            lambda: em.fit(ListDataSetIterator(DataSet(x, y).batch_by(32)),
                           n_epochs=2, resume=True,
                           async_prefetch=False),
            checkpoint=ck, world=1)
        ck.ckpt.close()
    if elastic.labels(direction="shrink").value - shrink0 < 1:
        problems.append("world=2 checkpoint fleet-resumed at world=1 "
                        "counted no elastic shrink")

    # -- SLO error-budget engine (ISSUE 15): an induced burn must be
    # observed FIRING on a real aggregated scrape, then RESOLVING
    # once the bleeding stops; one explicit postmortem bundle proves
    # the flight-recorder dump path end to end --------------------
    from deeplearning4j_tpu.telemetry.slo import AlertEngine, SLOSpec
    sreg = telemetry.MetricsRegistry()
    sfam = sreg.counter("fleet_requests_total",
                        labelnames=("tenant", "outcome"))
    sfam.labels(tenant="smoke", outcome="admitted")
    sfam.labels(tenant="smoke", outcome="failed")
    slo_eng = AlertEngine(
        [SLOSpec("smoke-avail", target=0.9, window_s=600.0,
                 windows=[(0.1, 0.3, 1.5, "page")])],
        registry=telemetry.MetricsRegistry())
    with tempfile.TemporaryDirectory() as d:
        telemetry.publish_beacon(d, "slohost", registry=sreg)
        fview = telemetry.FleetRegistry(d, stale_after_s=3600.0,
                                        alerts=slo_eng)
        with telemetry.start_metrics_server(fview, port=0) as srv:
            base = f"http://127.0.0.1:{srv.port}"
            urllib.request.urlopen(base + "/metrics",
                                   timeout=5).read()   # primes
            sfam.labels(tenant="smoke", outcome="failed").inc(9)
            sfam.labels(tenant="smoke", outcome="admitted").inc(1)
            telemetry.publish_beacon(d, "slohost", registry=sreg)
            time.sleep(0.35)           # long-window coverage accrues
            slo_body = urllib.request.urlopen(
                base + "/metrics", timeout=5).read().decode()
            alerts_doc = json.loads(urllib.request.urlopen(
                base + "/alerts", timeout=5).read().decode())
            problems += missing_series(slo_body, SLO_SERIES)
            if alerts_doc.get("firing") != ["smoke-avail"]:
                problems.append("induced burn not firing at /alerts: "
                                f"{alerts_doc.get('firing')}")
            # the bleeding stops: clean traffic must RESOLVE it
            sfam.labels(tenant="smoke", outcome="admitted").inc(500)
            telemetry.publish_beacon(d, "slohost", registry=sreg)
            time.sleep(0.35)
            alerts_doc = json.loads(urllib.request.urlopen(
                base + "/alerts", timeout=5).read().decode())
            states = {a["slo"]: a["state"]
                      for a in alerts_doc.get("alerts", ())}
            if states.get("smoke-avail") != "resolved":
                problems.append("induced burn did not resolve after "
                                f"clean traffic: {states}")
            # ISSUE 16: the store's accounting on the aggregated
            # scrape, and a live /query over the recorded history —
            # the admitted counter's rate must be positive and
            # consistent with its delta over the same window
            problems += missing_series(slo_body, TSDB_SERIES)
            qbase = (base + "/query?series=fleet_requests_total"
                     "&tenant=smoke&outcome=admitted")
            qr = json.loads(urllib.request.urlopen(
                qbase, timeout=5).read().decode())
            pts = [p for r in qr.get("results", ())
                   for p in r.get("points", ())]
            if len(pts) < 2:
                problems.append("/query range over the admitted "
                                f"counter held {len(pts)} samples "
                                f"(< 2): {qr}")
            qd = json.loads(urllib.request.urlopen(
                qbase + "&func=delta", timeout=5).read().decode())
            qrt = json.loads(urllib.request.urlopen(
                qbase + "&func=rate", timeout=5).read().decode())
            dv = [r["value"] for r in qd.get("results", ())
                  if r.get("value") is not None]
            rv = [r["value"] for r in qrt.get("results", ())
                  if r.get("value") is not None]
            if not dv or dv[0] <= 0:
                problems.append("/query delta over the admitted "
                                f"counter not positive: {qd}")
            if not rv or rv[0] <= 0:
                problems.append("/query rate over the admitted "
                                f"counter not positive: {qrt}")
            if dv and rv and len(pts) >= 2:
                span = pts[-1][0] - pts[0][0]
                if span > 0 and (abs(rv[0] * span - dv[0])
                                 > 1e-6 + 0.1 * abs(dv[0])):
                    problems.append(
                        f"/query rate {rv[0]:g} inconsistent with "
                        f"delta {dv[0]:g} over {span:.3f}s")
            try:
                urllib.request.urlopen(base + "/query?series=",
                                       timeout=5)
                problems.append("/query with an empty series "
                                "selector did not answer 400")
            except urllib.error.HTTPError as e:
                if e.code != 400:
                    problems.append("/query with an empty series "
                                    f"selector answered {e.code}")
        # one explicit postmortem bundle: the dump path end to end
        recorder = telemetry.get_flight_recorder()
        recorder.install_dump(d, host="smokehost", alerts=slo_eng)
        bundle_path = recorder.request_dump("check_telemetry smoke")
        recorder.uninstall_dump()
        from deeplearning4j_tpu.telemetry import flightrec
        if bundle_path is None or flightrec.list_bundles(d) != [
                bundle_path]:
            problems.append("explicit request_dump produced no "
                            "postmortem bundle")
        else:
            bdoc = flightrec.load_bundle(bundle_path)
            if not bdoc.get("events"):
                problems.append("postmortem bundle carries no "
                                "flight-recorder events")
            if (bdoc.get("slo") or {}).get("specs") != 1:
                problems.append("postmortem bundle carries no SLO "
                                "state")

    # -- mesh-sharded serving (ISSUE 17): a tp=2 replica over two
    # virtual devices must decode byte-identical to the single-chip
    # server, report the GLOBAL pool's block counts (the autoscaler /
    # placement view), and attribute its decode phase to EVERY chip of
    # the slice; a mixed fleet puts the per-replica slice gauge on the
    # wire ----------------------------------------------------------
    import jax
    if jax.device_count() < 2:
        problems.append(f"mesh smoke needs >= 2 devices, have "
                        f"{jax.device_count()}")
    else:
        tp_slice = jax.devices()[:2]
        mp = np.asarray([3, 1, 4, 1, 5, 9], np.int32)
        with GenerationServer(gpt, n_slots=2, max_len=32) as gm0:
            mesh_ref = gm0.submit(mp, n_new=4, timeout=300)
            free_plain = gm0.stats()["free_blocks"]
        with GenerationServer(gpt, n_slots=2, max_len=32,
                              devices=tp_slice) as gm:
            mesh_out = gm.submit(mp, n_new=4, timeout=300)
            mst = gm.stats()
        if not np.array_equal(mesh_out, mesh_ref):
            problems.append("tp=2 decode diverged from the "
                            "single-chip decode of the same prompt")
        if mst["tp"] != 2 or mst["devices"] != [
                f"{d.platform}:{d.id}" for d in tp_slice]:
            problems.append(f"sharded server stats misreport the "
                            f"slice: tp={mst['tp']} "
                            f"devices={mst['devices']}")
        if mst["free_blocks"] != free_plain:
            problems.append(
                "sharded pool free-KV view is not the GLOBAL block "
                f"count ({mst['free_blocks']} != {free_plain}) — the "
                "autoscaler would see a per-shard fraction")
        # mixed fleet: single-chip replica 0 + tp=2 replica 1 — the
        # slice gauge needs no traffic, it is set at construction
        with ServingFleet(gpt, n_replicas=2, n_slots=2, max_len=32,
                          devices=[None, tp_slice]):
            pass

    # -- static analysis: lint series on the wire ----------------------
    emit_analysis_series(problems)

    # -- scrape over HTTP ----------------------------------------------
    body = scrape_body(telemetry, registry)

    series = {line.rsplit(" ", 1)[0] for line in body.splitlines()
              if line and not line.startswith("#")}
    if len(series) < 20:
        problems.append(f"only {len(series)} series exposed (< 20)")
    for fam in registry.families():
        if fam.kind != "histogram":
            continue
        for lv, child in fam._items():
            s = child.state()[2]
            if math.isnan(s):
                problems.append(f"histogram {fam.name}{lv} sum is NaN")
    required = [
        'inference_latency_seconds_bucket',
        'flash_route_total{path="xla"}',
        "mfu ",
        "train_data_wait_seconds_bucket",
        "train_step_dispatch_seconds_bucket",
        "generation_server_admitted_total",
        "generation_server_retired_total",
        "generation_server_ttft_seconds_bucket",
        "generation_server_slots_busy",
        "generation_server_slot_occupancy_bucket",
        "generation_server_ticks_total",
        # multi-tick decode scan series: the solo request above
        # guarantees a k=4 fused scan ran and was host-polled once
        "generation_server_host_syncs_total",
        'generation_server_scan_ticks_total{k="4"}',
        "generation_server_tokens_emitted_total",
        "generation_server_slot_ticks_total",
        'generation_server_sched_host_seconds_total{phase="admit"}',
        'generation_server_sched_host_seconds_total{phase="retire"}',
        # one packed array each way a dispatch (ISSUE 32)
        'generation_server_host_transfers_total{site="scan",dir="d2h"}',
        'generation_server_host_transfers_total{site="admit",dir="h2d"}',
        'generation_server_dispatches_total{program="scan"}',
        'generation_server_dispatches_total{program="admit"}',
        # continuous device-phase profile (ISSUE 13): the serve/spec
        # runs above sampled all three serve phases on this process
        'fleet_device_phase_seconds_bucket{device="cpu:0",'
        'phase="decode_tick"',
        'fleet_device_phase_seconds_bucket{device="cpu:0",'
        'phase="prefill"',
        'fleet_device_phase_seconds_bucket{device="cpu:0",'
        'phase="verify"',
        "fleet_xprof_captures_total",
        "fleet_xprof_capture_files",
    ] + PAGED_KV_SERIES + TIERED_KV_SERIES + SPEC_SERIES \
      + FLEET_SERIES + RESILIENCE_SERIES + ANALYSIS_SERIES \
      + FORECAST_SERIES + FLIGHT_SERIES + MESH_SERIES \
      + DEGRADE_SERIES
    problems += missing_series(body, required)
    if lat.count - lat_before != 16:
        problems.append(
            f"latency histogram grew {lat.count - lat_before} != 16")

    # -- trace export + report embedding -------------------------------
    with tempfile.TemporaryDirectory() as d:
        trace = tracer.export_jsonl(os.path.join(d, "trace.jsonl"))
        if os.path.getsize(trace) == 0:
            problems.append("span trace export is empty")
        out = render_report(storage, os.path.join(d, "report.html"),
                            trace_path="trace.jsonl")
        html = open(out).read() if out else ""
        if "Telemetry" not in html or "trace.jsonl" not in html:
            problems.append("report missing telemetry table or trace link")

    print(json.dumps({"ok": not problems, "series": len(series),
                      "problems": problems}))
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
