"""Unified telemetry: process-wide metrics registry + span tracing.

The production-observability subsystem the training-only ``ui`` stack
lacked (round-5 review rec 10: serving-saturation visibility).  Three
pieces, all stdlib-only:

* ``registry``  — thread-safe Counter/Gauge/Histogram families with
  Prometheus text exposition, jsonl snapshots, and driver-side
  snapshot merging;
* ``tracing``   — nestable host-side spans exported as Chrome-trace
  jsonl (``about://tracing``/Perfetto-loadable), plus TRACKED spans
  (``begin``/``end`` from any thread, close-on-owner-death) carrying
  request trace ids across components;
* ``exposition``— stdlib ``http.server`` scrape endpoint;
* ``fleet``     — the cross-worker plane (ISSUE 12): per-host metric
  beacons pushed into a shared dir (or over ``jax.distributed``
  collectives) and ``FleetRegistry`` aggregation into ONE
  ``{host=}``-tagged scrape with rollups, reset detection and
  staleness marking;
* ``profiling`` — continuous per-device profiling (ISSUE 13): a
  sampling ``DeviceProfiler`` wraps the hot dispatch sites (decode
  tick, verify, prefill, optimizer step) with device-time measurement
  into ``fleet_device_phase_seconds{device=,phase=}`` plus an
  on-demand XProf capture trigger whose summary beacons fleet-wide;
  the beacons also ship closed request spans, which ``FleetRegistry``
  stitches into per-request trees in a ``FleetTraceStore``;
* ``slo``       — the plane's CONSUMER (ISSUE 15): declarative
  ``SLOSpec`` objectives over the already-emitted request series, an
  error-budget accountant, and a multi-window burn-rate
  ``AlertEngine`` whose state is ordinary metric families (beacons
  like everything else) and serves as JSON at ``/alerts``;
* ``flightrec`` — the per-host black box (ISSUE 15): a lock-cheap
  bounded ring of admission/dispatch/spill/watchdog/scale events;
  watchdog trips, chaos kills and preemptions freeze it — with the
  tracer's open spans, a metric snapshot, pre-crash metric HISTORY
  and the SLO state — into atomic postmortem bundles
  ``scripts/postmortem.py`` renders as a merged timeline;
* ``gc_watch``  — Python's garbage collections: seconds
  and collections by generation, and from generation 1 up a
  ``gc/collect`` profiler host span; its ``gc.callbacks`` hook takes
  no lock and the counters catch up at each read of the registry;
* ``tsdb``      — the embedded time-series store (ISSUE 16): bounded
  per-series history rings (raw window + downsampled older tier)
  recorded each scrape/beacon cycle, range reads with
  ``rate``/``delta``/``quantile_over_time``, the ``/query`` endpoint
  beside ``/metrics``/``/traces``/``/alerts`` — and the ONE history
  substrate the SLO engine, the backlog forecaster and the
  autoscaler's windowed signals all read through.

Instrumented in-tree: ``optimize.fit_loop`` (step/data-wait split,
iteration/epoch/example counters), ``parallel.trainer`` and
``parallel.pipeline`` (per-worker step counters, dispatch spans, bubble
fraction), ``parallel.inference`` (latency histogram, queue depth,
batch occupancy, padding waste, shed/timeout counters),
``models.generation`` (tokens emitted, decode steps/s), and
``kernels.flash_attention`` (``flash_route_total{path=...}`` — silent
fallbacks off the flash path are a metric, not a debug deque).

Module-level ``counter``/``gauge``/``histogram`` register on ONE
process-default registry so every subsystem lands on the same scrape
surface; ``TelemetryListener`` bridges the registry into the existing
``set_listeners()`` machinery.
"""
from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.telemetry.registry import (
    DEFAULT_BUCKETS, RATIO_BUCKETS, Counter, Gauge, Histogram,
    MetricsRegistry, parse_series)
from deeplearning4j_tpu.telemetry.tracing import (FleetTraceStore, Span,
                                                  SpanTracer)
from deeplearning4j_tpu.telemetry.exposition import (
    MetricsServer, start_metrics_server)
from deeplearning4j_tpu.telemetry.listener import TelemetryListener
from deeplearning4j_tpu.telemetry.fleet import (
    FleetRegistry, MetricsBeacon, exchange_snapshots, publish_beacon)
from deeplearning4j_tpu.telemetry.profiling import DeviceProfiler
from deeplearning4j_tpu.telemetry.flightrec import FlightRecorder
from deeplearning4j_tpu.telemetry.slo import (AlertEngine, CommandSink,
                                              SLOSpec, WebhookFileSink)
from deeplearning4j_tpu.telemetry.tsdb import TimeSeriesStore
from deeplearning4j_tpu.telemetry import gc_watch

_REGISTRY = MetricsRegistry()
gc_watch.install(_REGISTRY)
_TRACER = SpanTracer()
_PROFILER = DeviceProfiler(_REGISTRY)
_FLIGHTREC = FlightRecorder()
_TSDB = TimeSeriesStore()


def get_registry() -> MetricsRegistry:
    """The process-wide default registry every in-tree metric lives in."""
    return _REGISTRY


def get_tracer() -> SpanTracer:
    """The process-wide default span tracer."""
    return _TRACER


def get_profiler() -> DeviceProfiler:
    """The process-wide sampling device profiler (ISSUE 13) the hot
    dispatch sites report into."""
    return _PROFILER


def get_flight_recorder() -> FlightRecorder:
    """The process-wide flight recorder (ISSUE 15): the bounded ring
    of admission/dispatch/spill/watchdog/scale events the hot sites
    feed, and the postmortem-bundle writer the crash paths trip."""
    return _FLIGHTREC


def get_tsdb() -> TimeSeriesStore:
    """The process-wide embedded time-series store (ISSUE 16):
    recorded per scrape/beacon cycle, queried at ``/query``, and the
    pre-crash history source for postmortem bundles."""
    return _TSDB


def counter(name: str, documentation: str = "",
            labelnames: Sequence[str] = ()) -> Counter:
    return _REGISTRY.counter(name, documentation, labelnames)


def gauge(name: str, documentation: str = "",
          labelnames: Sequence[str] = ()) -> Gauge:
    return _REGISTRY.gauge(name, documentation, labelnames)


def histogram(name: str, documentation: str = "",
              labelnames: Sequence[str] = (),
              buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
    return _REGISTRY.histogram(name, documentation, labelnames, buckets)


def span(name: str, **args):
    """``with telemetry.span("phase/thing"): ...`` on the default tracer."""
    return _TRACER.span(name, **args)


__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "SpanTracer",
    "Span", "MetricsServer", "start_metrics_server", "TelemetryListener",
    "FleetRegistry", "FleetTraceStore", "MetricsBeacon", "publish_beacon",
    "exchange_snapshots", "parse_series", "DeviceProfiler",
    "FlightRecorder", "AlertEngine", "SLOSpec", "WebhookFileSink",
    "CommandSink", "TimeSeriesStore",
    "DEFAULT_BUCKETS", "RATIO_BUCKETS",
    "get_registry", "get_tracer", "get_profiler", "get_flight_recorder",
    "get_tsdb", "counter", "gauge", "histogram", "span",
]
