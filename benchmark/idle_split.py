"""The device's idle time in a traced sub-window, put down to the host
spans that were open in it: which scheduler phase (``serve/admit``,
``launch``, ``poll``, ``retire``) or Python collection (``gc/collect``)
each idle instant fell in.

``idle_under`` is the reduction and ``trace_idle_under`` the reader of
the ``idle_share.*`` files under ``layer_metrics/``; a cell reads them
once ``readers.READERS`` holds the reader and
``trace_reduce.HOST_SPAN_PREFIX`` keeps ``gc/`` spans (PERF.md, Open
questions).  Checked on hand-built traces in
``tests/benchmark_suite/test_idle_split.py``.
"""
from __future__ import annotations

import heapq

from benchmark import trace_reduce


def _idle(busy: list, lo: float, hi: float) -> list:
    """[(start, end)]: the complement of merged ``busy`` in [lo, hi]."""
    out, t = [], lo
    for start, end in busy:
        if start > t:
            out.append((t, min(start, hi)))
        t = max(t, end)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def _attribute(idle: list, spans: list, out: dict, weight: float) -> None:
    """Add ``weight`` x each idle interval's length to ``out`` under the
    shortest of ``spans`` ((start, end, name)) open at each instant."""
    edges = [(a, 1, -1) for a, _ in idle] + [(b, 0, -1) for _, b in idle]
    for i, (start, end, _) in enumerate(spans):
        edges += [(start, 1, i), (end, 0, i)]
    edges.sort()
    open_, closed, idle_depth, t_prev = [], set(), 0, None
    for t, opening, i in edges:
        if idle_depth and t > t_prev:
            while open_ and open_[0][1] in closed:
                heapq.heappop(open_)
            name = spans[open_[0][1]][2] if open_ else "none"
            out[name] = out.get(name, 0.0) + weight * (t - t_prev)
        if i < 0:
            idle_depth += 1 if opening else -1
        elif opening:
            heapq.heappush(open_, (spans[i][1] - spans[i][0], i))
        else:
            closed.add(i)
        t_prev = t


def idle_under(trace: dict, window_s: float, among) -> dict:
    """{span name: seconds}: the device idle time of ``[m0, m0 +
    window_s]`` (``m0`` the ``WINDOW_MARK`` span's start; the first
    operation's where the trace has none) -- the complement of the union
    of the ``XLA Ops`` intervals, both edges included, so that the parts
    sum to ``window_s - busy_seconds`` -- each instant under the
    innermost (shortest) open host span whose name starts with one of
    ``among``, else under ``"none"``; averaged over the devices."""
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane")
    m0 = next((start for name, start, _ in trace["host_spans"]
               if name == trace_reduce.WINDOW_MARK), None)
    spans = [(start, start + dur, name)
             for name, start, dur, *_ in trace["host_spans"]
             if name.startswith(tuple(among))]
    out = {}
    for lines in devices.values():
        busy = trace_reduce.busy_intervals(lines.get(trace_reduce.OPS_LINE, []))
        lo = m0 if m0 is not None else (busy[0][0] if busy else 0.0)
        _attribute(_idle(busy, lo, lo + window_s), spans, out,
                   1.0 / len(devices))
    return out


def trace_idle_under(ctx, a):
    """``scale`` x the idle seconds under the span ``span`` (of those
    whose names start with one of ``among``) / the sub-window.  None
    where no such span is in the trace -- unless the registry holds the
    family ``known_by`` names: a program that counts it would have
    opened the span, so none in the sub-window reads 0."""
    trace, window_s = ctx["trace"], ctx["facts"]["window_s"]
    if not any(name == a["span"] for name, *_ in trace["host_spans"]):
        family = a.get("known_by")
        counted = family and any(series.split("{")[0] == family
                                 for series in ctx["after"]["counters"])
        return 0.0 if counted else None
    key = ("idle_under", tuple(a["among"]))
    if key not in ctx:
        ctx[key] = idle_under(trace, window_s, a["among"])
    return a.get("scale", 1.0) * ctx[key].get(a["span"], 0.0) / window_s
