"""Python's garbage collections as counters and, from generation 1 up,
as a profiler host span.

:func:`install` (done once, for the process-default registry, when
``telemetry`` is imported) appends :func:`_on_gc` to ``gc.callbacks``:

* ``python_gc_seconds_total{generation}`` and
  ``python_gc_collections_total{generation}`` count every collection;
* a collection of generation >= 1 is also a host span ``gc/collect``
  (its generation in the args) on the thread that collected, on the
  profiler's clock, so while a profile runs it lands in the capture
  beside the device lines and the scheduler's ``serve/<phase>`` spans.
  A generation-0 collection costs two ``perf_counter`` calls and a
  float add.

NO LOCK IN THE CALLBACK.  A collection runs at whatever allocation
triggers it, on whatever thread, including one inside ``with
self._lock:`` of a registry child or of ``SpanTracer`` -- every one a
non-reentrant ``threading.Lock``, so a callback that took one would
deadlock its own thread.  The callback adds to module-level numbers
alone (one collection runs at a time, under the interpreter lock), and
:func:`sync` brings the counters up to them from outside any callback:
at every ``snapshot()`` and ``render_prometheus()`` of the registry,
and at each of the generation scheduler's phase switches.
"""
from __future__ import annotations

import gc
import threading
import time

from deeplearning4j_tpu.telemetry.tracing import _profiler_annotation

GENERATIONS = (0, 1, 2)

#: seconds and collections by generation: written by the callback only
_seconds = [0.0] * len(GENERATIONS)
_count = [0] * len(GENERATIONS)
_t0 = 0.0
#: the open ``gc/collect`` span of a generation >= 1 collection
_span = None

#: what the counters were last brought up to, and their children
_pushed_seconds = [0.0] * len(GENERATIONS)
_pushed_count = [0] * len(GENERATIONS)
_children = None
_sync_lock = threading.Lock()


def _open_span(generation: int):
    try:
        span = _profiler_annotation("gc/collect", generation=generation)
        span.__enter__()
        return span
    except Exception:    # a collection inside jax's own import, or at exit
        return None


def _on_gc(phase: str, info: dict) -> None:
    global _t0, _span
    generation = info["generation"]
    if phase == "start":
        if generation:
            _span = _open_span(generation)
        _t0 = time.perf_counter()
        return
    _seconds[generation] += time.perf_counter() - _t0
    _count[generation] += 1
    if _span is not None:
        span, _span = _span, None
        span.__exit__(None, None, None)


def seconds_total() -> float:
    """Seconds all collections took so far, every generation, every
    thread (read without a lock: the numbers only grow)."""
    return sum(_seconds)


def sync() -> None:
    """Bring the counters up to the callback's numbers; never call it
    from a ``gc`` callback."""
    if _children is None or _count == _pushed_count:
        return
    with _sync_lock:
        secs, counts = _children
        for g in GENERATIONS:
            s, n = _seconds[g], _count[g]
            if n != _pushed_count[g]:
                secs[g].inc(s - _pushed_seconds[g])
                counts[g].inc(n - _pushed_count[g])
                _pushed_seconds[g], _pushed_count[g] = s, n


def install(registry) -> None:
    """Count collections into ``registry`` and hook ``gc``; a second
    call does nothing."""
    global _children
    if _children is not None:
        return
    secs = registry.counter(
        "python_gc_seconds_total",
        "seconds Python's garbage collector ran, by generation (every "
        "thread; a collection of generation >= 1 is also a gc/collect "
        "host span in a running profile)", labelnames=("generation",))
    counts = registry.counter(
        "python_gc_collections_total",
        "collections Python's garbage collector ran, by generation",
        labelnames=("generation",))
    _children = ([secs.labels(generation=g) for g in GENERATIONS],
                 [counts.labels(generation=g) for g in GENERATIONS])
    registry.add_pre_read_hook(sync)
    gc.callbacks.append(_on_gc)
