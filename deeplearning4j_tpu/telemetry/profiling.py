"""Continuous per-device profiling on the beacon plane (ISSUE 13).

The host-side registry answers "where does PYTHON time go"; XProf
(``ui.ProfilerListener``) answers "where does DEVICE time go" — but
its traces are host-local files a fleet scrape never sees.  This
module is the bridge, in the continuous-profiling shape the
MLPerf/XProf lineage uses: a LOW-OVERHEAD sampling profiler wraps the
hot dispatch sites (decode tick, speculative verify pass, prefill
chunk, optimizer step) with device-time measurement and folds samples
into ordinary registry families, so ``MetricsBeacon`` ships them and
the ONE fleet scrape gains
``fleet_device_phase_seconds{host=,device=,phase=}`` with rollups.

* **measurement** — :meth:`DeviceProfiler.measure` times the dispatch
  + host sync of a block.  Sites that already sync (the decode tick's
  poll) pay nothing extra; an async site (the optimizer step) hands
  its output to :meth:`_Measure.ready`, which
  ``jax.block_until_ready``-s it ONLY when this call is sampled —
  1-in-``every`` dispatches pays the sync, the rest stay fully async
  (the sampling that makes "continuous" affordable); a site whose
  thread may not wait at all (the serving scheduler's prefill) asks
  :meth:`DeviceProfiler.sampled` and closes its sample with
  :meth:`DeviceProfiler.observe` at its next sync;
* **fold** — samples land in the per-``(device, phase)`` histogram;
  :meth:`top_ops` ranks phases by cumulative device seconds (count,
  total, p50/p99) — the top-K op summary a fleet dashboard shows;
* **on-demand XProf** — :meth:`request_xprof` arms a real
  ``jax.profiler`` trace capture around the next N sampled
  dispatches.  The RAW trace stays a host-local artifact (point
  XProf/TensorBoard at ``log_dir``); its SUMMARY (file count, bytes,
  captured wall seconds) lands in ``fleet_xprof_*`` series that
  beacon fleet-wide — an operator sees from the fleet scrape that the
  capture ran and where to fetch it.

Thread-safe: the sampling counters and the XProf arm/active state
mutate only under ``self._lock``; the registry families carry their
own per-child locks.  ``jax`` imports are lazy — constructing a
profiler (and ``observe``) never initializes a backend.
"""
from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from typing import Dict, List, Optional

from deeplearning4j_tpu.telemetry.registry import MetricsRegistry

log = logging.getLogger("deeplearning4j_tpu")

#: the in-tree instrumented phases (callers may add their own)
PHASES = ("decode_tick", "verify", "prefill", "optimizer_step")


def _device_label() -> str:
    """``platform:id`` of the default device (one process profiles the
    device(s) it dispatches to; multi-chip splits arrive with the
    mesh-sharded tick)."""
    try:
        import jax
        dev = jax.devices()[0]
        return f"{dev.platform}:{dev.id}"
    except Exception:               # pragma: no cover - no backend
        return "unknown:0"


class _Measure:
    """The handle :meth:`DeviceProfiler.measure` yields.  ``sampled``
    tells the site whether THIS dispatch is being timed; ``ready``
    blocks on the given tree only then — the async fast path stays
    async."""

    __slots__ = ("sampled",)

    def __init__(self, sampled: bool):
        self.sampled = sampled

    def ready(self, tree) -> None:
        if self.sampled and tree is not None:
            import jax
            jax.block_until_ready(tree)


class DeviceProfiler:
    """Sampling device-time profiler feeding the fleet metric plane.

    >>> prof = telemetry.get_profiler()
    >>> with prof.measure("decode_tick"):
    ...     out = dispatch(...)      # site already host-syncs
    >>> with prof.measure("prefill") as m:
    ...     out = dispatch(...)
    ...     m.ready(out)             # sync only when sampled
    >>> prof.request_xprof("/tmp/xprof", dispatches=3)   # on demand
    >>> prof.top_ops(k=3)            # ranked device-time summary
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 sample_every: int = 1):
        if registry is None:
            from deeplearning4j_tpu import telemetry
            registry = telemetry.get_registry()
        self.registry = registry
        self.sample_every = max(1, int(sample_every))
        self._lock = threading.Lock()
        self._calls: Dict[str, int] = {}
        self._device: Optional[str] = None
        # XProf arm/active state under its OWN lock: start_trace can
        # take long (profiler backend init) and must never run under
        # — or make anyone wait on — the sampling lock every
        # measure() takes on the hot path
        self._xprof_lock = threading.Lock()
        self._xprof_dir: Optional[str] = None     # armed target
        self._xprof_starting = False              # claim flag
        self._xprof_active_dir: Optional[str] = None
        self._xprof_left = 0
        self._xprof_t0: Optional[float] = None
        self._hist = registry.histogram(
            "fleet_device_phase_seconds",
            "sampled device time per dispatch phase (dispatch -> "
            "host-sync complete): decode_tick, verify (speculative "
            "draft+verification), prefill (admission chunk), "
            "optimizer_step — the per-device timing the fleet scrape "
            "aggregates {host=,device=,phase=}",
            labelnames=("device", "phase"))
        self._skipped = registry.counter(
            "fleet_device_phase_skipped_total",
            "dispatches the sampling profiler let pass unmeasured "
            "(1-in-N sampling keeps async sites async)",
            labelnames=("phase",))
        self._xprof_captures = registry.counter(
            "fleet_xprof_captures_total",
            "on-demand jax.profiler trace captures completed on this "
            "host (the raw trace stays local; this summary beacons)")
        self._xprof_bytes = registry.gauge(
            "fleet_xprof_capture_bytes",
            "total bytes the last XProf capture wrote under its "
            "log_dir")
        self._xprof_files = registry.gauge(
            "fleet_xprof_capture_files",
            "files the last XProf capture wrote (trace shards, "
            "xplane protos)")
        self._xprof_seconds = registry.gauge(
            "fleet_xprof_capture_seconds",
            "wall seconds the last XProf capture window spanned")

    # -- measurement ---------------------------------------------------
    def device(self) -> str:
        with self._lock:
            if self._device is None:
                self._device = _device_label()
            return self._device

    @contextlib.contextmanager
    def measure(self, phase: str, every: Optional[int] = None,
                devices=None):
        """Time one dispatch of ``phase`` (1-in-``every`` sampling;
        defaults to the profiler-wide rate).  An armed XProf capture
        forces sampling so the capture window is always timed.

        ``devices`` (ISSUE 17): an iterable of ``platform:id`` labels
        — a MESH-SHARDED dispatch runs on every chip of the replica's
        slice simultaneously, so the one wall-time sample folds into
        EACH listed device's series (per-device phase attribution
        across the slice); None keeps the single default-device
        label."""
        capturing = self._xprof_participate()
        sampled = self.sampled(phase, every, force=capturing)
        m = _Measure(sampled)
        t0 = time.perf_counter() if sampled else 0.0
        try:
            yield m
        finally:
            if sampled:
                dt = time.perf_counter() - t0
                for dev in (devices if devices else (None,)):
                    self.observe(phase, dt, device=dev)
            if capturing:
                self._xprof_end()

    def sampled(self, phase: str, every: Optional[int] = None,
                force: bool = False) -> bool:
        """Count one dispatch of ``phase``; True for the 1 in ``every``
        (or ``force``) to time.  :meth:`measure` asks it; so does a
        site that must not wait for its own dispatch and hands the
        sample to :meth:`observe` where a later host sync shows the
        device past it (the serving scheduler's prefill)."""
        phase = str(phase)
        every = self.sample_every if every is None else max(1, int(every))
        with self._lock:
            n = self._calls.get(phase, 0) + 1
            self._calls[phase] = n
        if force or n % every == 0:
            return True
        self._skipped.labels(phase=phase).inc()
        return False

    def observe(self, phase: str, seconds: float,
                device: Optional[str] = None) -> None:
        """Fold one device-time sample (the ``measure`` sink; also the
        direct entry for sites that time themselves)."""
        self._hist.labels(device=device or self.device(),
                          phase=str(phase)).observe(float(seconds))

    # -- summaries -----------------------------------------------------
    def top_ops(self, k: Optional[int] = None) -> List[dict]:
        """Phases ranked by cumulative device seconds across devices —
        the top-K summary ("which op class owns this device").  Reads
        the SAME histogram family the scrape exposes, so the local
        answer and the fleet answer can never disagree."""
        out = []
        for lv, child in self._hist._items():
            device, phase = lv
            _u, _c, total, count = child.state()
            if not count:
                continue
            out.append({"device": device, "phase": phase,
                        "seconds": total, "samples": count,
                        "p50": child.percentile(0.50),
                        "p99": child.percentile(0.99)})
        out.sort(key=lambda d: d["seconds"], reverse=True)
        return out if k is None else out[:int(k)]

    # -- on-demand XProf capture ---------------------------------------
    def request_xprof(self, log_dir, dispatches: int = 1) -> None:
        """Arm a ``jax.profiler`` trace capture around the next
        ``dispatches`` measured dispatches (any phase).  Idempotent
        while armed/active: a second request before the first capture
        finishes is ignored (one capture at a time — captures are
        heavyweight by design, which is why they are on-demand while
        the sampling histograms are continuous)."""
        with self._xprof_lock:
            if (self._xprof_dir is not None or self._xprof_starting
                    or self._xprof_t0 is not None):
                log.warning("DeviceProfiler: XProf capture already "
                            "armed/active; ignoring request")
                return
            self._xprof_dir = str(log_dir)
            self._xprof_left = max(1, int(dispatches))

    def xprof_armed(self) -> bool:
        with self._xprof_lock:
            return (self._xprof_dir is not None or self._xprof_starting
                    or self._xprof_t0 is not None)

    def _xprof_participate(self) -> bool:
        """Join the capture window: the FIRST measured dispatch after
        arming claims the start and runs ``start_trace`` OUTSIDE the
        locks (it can take long — other dispatch threads must never
        queue behind it; they simply don't participate until the
        trace is live).  Returns True while this dispatch is inside
        the window — the caller must balance with ``_xprof_end``."""
        with self._xprof_lock:
            if self._xprof_t0 is not None:
                return True               # window already open
            if self._xprof_dir is None or self._xprof_starting:
                return False
            log_dir = self._xprof_dir     # claim the start
            self._xprof_dir = None
            self._xprof_starting = True
        try:
            import jax
            jax.profiler.start_trace(log_dir)
        except Exception:
            log.exception("DeviceProfiler: start_trace failed; "
                          "disarming the capture")
            with self._xprof_lock:
                self._xprof_starting = False
            return False
        with self._xprof_lock:
            self._xprof_starting = False
            self._xprof_active_dir = log_dir
            self._xprof_t0 = time.perf_counter()
        return True

    def _xprof_end(self) -> None:
        with self._xprof_lock:
            if self._xprof_t0 is None:
                return
            self._xprof_left -= 1
            if self._xprof_left > 0:
                return
            log_dir = self._xprof_active_dir
            t0 = self._xprof_t0
            self._xprof_active_dir = None
            self._xprof_t0 = None
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:
            log.exception("DeviceProfiler: stop_trace failed")
            return
        self._summarize_capture(log_dir, time.perf_counter() - t0)

    def _summarize_capture(self, log_dir: str, wall_s: float) -> None:
        """The part of a capture that beacons: walk the trace dir and
        publish size/shape gauges (the raw artifact stays local)."""
        n_files = 0
        n_bytes = 0
        for root, _dirs, files in os.walk(log_dir):
            for name in files:
                n_files += 1
                try:
                    n_bytes += os.path.getsize(os.path.join(root, name))
                except OSError:
                    pass
        self._xprof_captures.inc()
        self._xprof_bytes.set(n_bytes)
        self._xprof_files.set(n_files)
        self._xprof_seconds.set(wall_s)
        log.info("DeviceProfiler: XProf capture -> %s (%d files, %d "
                 "bytes, %.3gs window)", log_dir, n_files, n_bytes,
                 wall_s)
