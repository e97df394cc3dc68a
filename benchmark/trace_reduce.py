"""From the profiler's ``.xplane.pb`` to numbers: the device's busy
time (the union of the intervals in which an operation ran), device
time by event-name pattern, the operations that took most time and the
longest idle gaps with what the host was doing in them.

A trace is reduced to plain lists first (``load``), so the arithmetic
below is checked on hand-built traces with no profiler
(``tests/benchmark_suite``).  Times are seconds on the profile's clock.
The capture is longer than the sub-window the driver times; every
reading is taken on the trace ``sub_window`` cut to it.
"""
from __future__ import annotations

import glob
import os
import re

#: the device plane's lines: operations, and whole jitted programs
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
#: host spans kept from a capture: the benchmark's own (TraceAnnotation)
#: and the program's scoped phases (``telemetry.span``: the scheduler's
#: ``serve/admit|tick|retire|idle``, the fit loop's ``train/step``)
HOST_SPAN_PREFIX = ("bench/", "serve/", "train/")
#: an operation's name in the trace is its whole HLO line: keep the
#: instruction's name, its shape and its opcode, not its operands
NAME_CHARS = 160
#: a loop, a branch or a call holds its body's operations as events of
#: their own on the same line: it is a container, not work to rank
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]* = |(?<![\w-])(while|conditional|call)\(")
#: the host span a traced driver opens where it takes its sub-window's
#: first mark (``trace0``): that moment on the device lines' clock
WINDOW_MARK = "bench/trace0"


def load(trace_dir: str) -> dict:
    """{"devices": {plane: {line: [(name, start_s, dur_s)]}},
    "host_spans": [(name, start_s, dur_s)]} of the newest capture."""
    from jax.profiler import ProfileData
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    devices, host = {}, []
    for plane in ProfileData.from_file(found[-1]).planes:
        if plane.name.startswith("/device:TPU:"):
            devices[plane.name] = {
                line.name: [(e.name[:NAME_CHARS], e.start_ns * 1e-9,
                             e.duration_ns * 1e-9) for e in line.events]
                for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                         for e in line.events
                         if e.name.startswith(HOST_SPAN_PREFIX)]
    return {"devices": devices, "host_spans": sorted(host, key=lambda e: e[1])}


def sub_window(trace: dict, window_s: float) -> dict:
    """The trace cut to ``[m0, m0 + window_s]``, ``m0`` the start of the
    ``WINDOW_MARK`` span: every device event and host span cut to its
    overlap with it, one with none dropped.  A device event gets a
    fourth field, whether its middle lies inside: ``time_of`` counts
    only those, so a ratio of events to their time carries no bias from
    the two cut at the edges.  The interval's length is the host's
    ``window_s``, so no busy time can exceed it, whatever the two clocks
    say.  A trace without the mark comes back unchanged."""
    m0 = next((start for name, start, _ in trace["host_spans"]
               if name == WINDOW_MARK), None)
    if m0 is None:
        return trace
    m1 = m0 + window_s

    def cut(events, flag: bool) -> list:
        out = []
        for name, start, dur, *_ in events:
            lo, hi = max(start, m0), min(start + dur, m1)
            if hi > lo or (hi == lo and dur == 0):
                inside = (m0 <= start + dur / 2 < m1,) if flag else ()
                out.append((name, lo, hi - lo) + inside)
        return out
    return {"devices": {plane: {line: cut(events, True)
                                for line, events in lines.items()}
                        for plane, lines in trace["devices"].items()},
            "host_spans": cut(trace["host_spans"], False)}


def busy_intervals(events) -> list:
    """Merged [start, end] intervals of (name, start, dur) events."""
    merged = []
    for _, start, dur, *_ in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return merged


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    per_device = [sum(e - s for s, e in busy_intervals(lines.get(OPS_LINE, [])))
                  for lines in trace["devices"].values()]
    if not per_device:
        raise ValueError("the trace holds no device plane")
    return sum(per_device) / len(per_device)


def time_of(trace: dict, pattern: str, line: str = OPS_LINE):
    """(device seconds, events) of the events on ``line`` whose name
    matches ``pattern``, averaged over the devices; an event cut by
    ``sub_window`` counts in ``events`` only where it says so.  Nothing
    counted: (None, 0) -- a reader then returns nothing, never 0."""
    rx = re.compile(pattern)
    total, count = 0.0, 0
    for lines in trace["devices"].values():
        for name, _, dur, *inside in lines.get(line, []):
            if rx.search(name):
                total, count = total + dur, count + (inside[0] if inside else 1)
    n = max(1, len(trace["devices"]))
    return (total / n, count // n) if count else (None, 0)


def op_group(name: str) -> str:
    """``%fusion.1683 = (pred[]{:T(512)}, bf16[1024,4096]{1,0:T(8,128)}) fusion(...``
    -> ``%fusion = (pred[], bf16[1024,4096]) fusion``: an instruction
    without its number and layouts, so the same operation of every
    layer and step falls into one group."""
    head, _, rest = name.partition(" = ")
    rest = re.sub(r"\{[^}]*\}?", "", rest).split(" %")[0]
    return (re.sub(r"[.\d]+$", "", head) + (" = " + rest[:90] if rest else "")).strip()


def top_ops(trace: dict, n: int = 10) -> list:
    """[[group, seconds], ...]: the device operations that took most."""
    by_name = {}
    for lines in trace["devices"].values():
        for name, _, dur, *_ in lines.get(OPS_LINE, []):
            if CONTAINER.search(name):
                continue
            key = op_group(name)
            by_name[key] = by_name.get(key, 0.0) + dur
    k = max(1, len(trace["devices"]))
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / k] for name, secs in ranked]


def idle_gaps(trace: dict, n: int = 10) -> list:
    """[[what the host was doing, seconds], ...]: the longest gaps
    between device operations on the first device, each named by the
    innermost kept host span that covers its middle."""
    if not trace["devices"]:
        return []
    lines = trace["devices"][sorted(trace["devices"])[0]]
    spans = busy_intervals(lines.get(OPS_LINE, []))
    gaps = sorted(((b[0] - a[1], (a[1] + b[0]) / 2)
                   for a, b in zip(spans, spans[1:])), reverse=True)[:n]
    out = []
    for length, mid in gaps:
        cover = [(dur, name) for name, start, dur in trace["host_spans"]
                 if start <= mid <= start + dur]
        out.append([min(cover)[1] if cover else "host:unannotated", length])
    return out
