"""The per-layer metrics' readers.  A metric is a JSON file under
``layer_metrics/`` that names one of the reductions below and its
arguments; a new metric that an existing reduction can read is a new
file.  Every reader works on the traced sub-window: ``ctx`` holds its
facts (``window_s``, ``steps``, ``tokens``, ``flops`` and what else
the cell's family counts for its kernels' costs), the registry
snapshots taken at its two ends, the loaded trace, the chip's peaks,
and the cell's family, shape and traffic.  A reader that finds nothing
to read returns None and the metric is left out of the line.
"""
from __future__ import annotations

from benchmark import costs, trace_reduce


def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation over ALL values; a
    failed request's infinite latency stays infinite."""
    import math
    v = sorted(float(x) for x in values)
    if not v:
        raise ValueError("no request finished in the window")
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if lo == hi or v[lo] == v[hi]:
        return v[lo]
    if math.isinf(v[hi]):
        return v[hi]
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def _series_delta(ctx, sel: dict):
    """Delta over the sub-window of one registry series, or of every
    labelled child of a family (``"family": name``), a histogram's
    ``sum`` or ``count`` when ``field`` says so."""
    kind = "histograms" if sel.get("field") else "counters"
    total, found = 0.0, False
    for snap, sign in ((ctx["after"], 1.0), (ctx["before"], -1.0)):
        for series, v in snap[kind].items():
            if series == sel.get("series") or (
                    "family" in sel and series.split("{")[0] == sel["family"]):
                total += sign * (v[sel["field"]] if sel.get("field") else v)
                found = found or sign > 0
    return total if found else None


def _per(ctx, per):
    if per is None:
        return 1.0
    if isinstance(per, dict):
        return _series_delta(ctx, per)
    return ctx["facts"].get(per)


def registry_delta(ctx, a):
    num, den = _series_delta(ctx, a["of"]), _per(ctx, a.get("per"))
    return None if num is None or not den else a.get("scale", 1.0) * num / den


def registry_ratio(ctx, a):
    num, den = _series_delta(ctx, a["num"]), _series_delta(ctx, a["den"])
    return None if num is None or not den else a.get("scale", 1.0) * num / den


def trace_busy_share(ctx, a):
    share = 100.0 * trace_reduce.busy_seconds(ctx["trace"]) / ctx["facts"]["window_s"]
    return 100.0 - share if a.get("idle") else share


def trace_time_of(ctx, a):
    """Device seconds of the events that match ``pattern`` (all busy
    time without one), divided by a fact, a registry delta, or -- with
    ``per_events_of`` -- by how often another operation ran in the same
    trace (its events / the shape entry ``each``: a kernel called once
    per layer and tick counts the ticks, with no edge between a
    counter's clock and the trace's)."""
    if a.get("pattern"):
        secs, _ = trace_reduce.time_of(ctx["trace"], a["pattern"],
                                       a.get("line", trace_reduce.OPS_LINE))
    else:
        secs = trace_reduce.busy_seconds(ctx["trace"])
    if "per_events_of" in a:
        of = a["per_events_of"]
        den = trace_reduce.time_of(ctx["trace"], of["pattern"])[1] / ctx["shape"][of["each"]]
    else:
        den = _per(ctx, a.get("per"))
    return None if secs is None or not den else a.get("scale", 1.0) * secs / den


def _kernel_cost(ctx, a, events: int):
    """A metric file's ``"cost": name`` is a cost function of the
    cell's family: operations and bytes of the kernel's ``events``."""
    family = ctx["family"]
    if a["cost"] not in family.KERNEL_COSTS:
        raise ValueError(f"unknown cost function {a['cost']!r}: family "
                         f"{family.__name__} has {sorted(family.KERNEL_COSTS)}")
    return family.KERNEL_COSTS[a["cost"]](ctx["shape"], ctx["facts"],
                                          ctx["traffic"], events, a)


def roofline_of(ctx, a):
    """100 x (the least time the chip could take) / (the kernel's time
    in the trace); which limit binds is kept under ``ctx['bounds']``."""
    secs, events = trace_reduce.time_of(ctx["trace"], a["pattern"])
    if secs is None:
        return None
    least, bound = costs.roofline_seconds(_kernel_cost(ctx, a, events), ctx["peak"])
    ctx.setdefault("bounds", {})[a["pattern"]] = bound
    return 100.0 * least / secs


def fact_percentile(ctx, a):
    """The ``q``-th percentile of a list the driver kept for the
    sub-window (``of``: ``ttft_s``)."""
    values = ctx["facts"].get(a["of"])
    return a.get("scale", 1.0) * percentile(values, a["q"]) if values else None


def mfu_of(ctx, a):
    f = ctx["facts"]
    return 100.0 * f["flops"] / f["window_s"] / ctx["peak"]["bf16_flops_per_s"]


READERS = {f.__name__: f for f in (registry_delta, registry_ratio,
                                   trace_busy_share, trace_time_of,
                                   roofline_of, mfu_of, fact_percentile)}


def read(metric: dict, ctx: dict):
    reader = metric["reader"]
    return READERS[reader["kind"]](ctx, reader.get("args", {}))
