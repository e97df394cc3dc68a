"""The yardstick's arithmetic, checked by hand: the trace reduction on a
hand-built trace and its cut to the traced sub-window, where the serving
loop takes that sub-window's marks, the cost functions on a tiny shape,
the percentile and rate with a stall in the window, every per-layer
reader on made-up facts, and BENCHMARK.json's names and files."""
import json
import os
import re
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import costs, drivers, readers, run, trace_reduce  # noqa: E402
from benchmark.families import post_ln  # noqa: E402

OPS = [("%while.4 = (s32[], bf16[24,2049,16,16,64]", 0.0, 1.5),  # holds the next two (name cut short)
       ("fusion.1", 0.0, 1.0), ("flash_kernel", 0.5, 1.0),   # overlap: 0..1.5
       ("fusion.1", 3.0, 0.5), ("flash_kernel", 5.5, 1.0)]   # gaps 1.5 and 2.0
TRACE = {"devices": {"/device:TPU:0": {
    "XLA Ops": OPS, "XLA Modules": [("jit_scan_fn(1)", 0.0, 1.5),
                                    ("jit_admit(2)", 3.0, 0.5)]}},
    "host_spans": [("bench/fit", 0.0, 10.0), ("bench/poll_sleep", 1.6, 1.0)]}
SHAPE = {"d": 8, "layers": 2, "heads": 2, "ff": 16, "vocab": 50,
         "max_len": 32, "n_out": 50}
PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_trace_reduction_on_a_hand_built_trace():
    assert trace_reduce.busy_intervals(OPS) == [[0.0, 1.5], [3.0, 3.5], [5.5, 6.5]]
    assert trace_reduce.busy_seconds(TRACE) == pytest.approx(3.0)
    assert trace_reduce.time_of(TRACE, "flash") == (pytest.approx(2.0), 2)
    assert trace_reduce.time_of(TRACE, "scan_fn", "XLA Modules") == (1.5, 1)
    assert trace_reduce.time_of(TRACE, "no_such_kernel") == (None, 0)
    assert trace_reduce.top_ops(TRACE, 1) == [["flash_kernel", 2.0]]
    assert trace_reduce.op_group(
        "%fusion.1683 = (pred[]{:T(512)}, bf16[1024,4096]{1,0:T(8,128)(2,1)}) "
        "fusion(bf16[16,512,4096]{2,1,0} %get-tuple-element.1457)") == \
        "%fusion = (pred[], bf16[1024,4096]) fusion(bf16[16,512,4096]"
    # each gap is named by the INNERMOST benchmark span over its middle
    assert trace_reduce.idle_gaps(TRACE) == [["bench/fit", 2.0],
                                             ["bench/poll_sleep", 1.5]]
    # the program's own scoped phases name a gap too, other spans do not
    spans = [("serve/retire", 4.0, 1.0), ("request/queue", 4.2, 0.2),
             ("train/step", 1.5, 1.0)]
    kept = [s for s in spans if s[0].startswith(trace_reduce.HOST_SPAN_PREFIX)]
    assert trace_reduce.idle_gaps(dict(TRACE, host_spans=kept)) == [
        ["serve/retire", 2.0], ["train/step", 1.5]]


def _marked(ops, m0=2.0, host=()):
    """A one-device trace with the sub-window's first mark at ``m0``."""
    return {"devices": {"/device:TPU:0": {"XLA Ops": list(ops)}},
            "host_spans": sorted([(trace_reduce.WINDOW_MARK, m0, 1e-6), *host],
                                 key=lambda e: e[1])}


IDLE = run.load("benchmark", "layer_metrics", "device_idle_share.serve.json")


# the sub-window is [2.0, 7.0]; "whole" is a device busy through a 10 s
# capture; at each edge an event straddles it with its middle inside or
# outside; 5.0 .. 6.0 lies inside whole
@pytest.mark.parametrize("ops,busy,kernel", [
    ([("flash", 0.0, 10.0)], 5.0, (5.0, 1)),
    ([("flash", 1.0, 3.0), ("flash", 5.0, 1.0)], 3.0, (3.0, 2)),
    ([("flash", 0.0, 3.0), ("flash", 5.0, 1.0)], 2.0, (2.0, 1)),
    ([("flash", 6.0, 1.5), ("flash", 5.0, 1.0)], 2.0, (2.0, 2)),
    ([("flash", 6.5, 2.0), ("flash", 5.0, 1.0)], 1.5, (1.5, 1)),
    ([("flash", 0.0, 1.5), ("flash", 8.0, 1.0), ("flash", 5.0, 1.0)], 1.0, (1.0, 1)),
], ids=["whole", "start_mid_in", "start_mid_out", "end_mid_in", "end_mid_out",
        "outside"])
def test_the_trace_is_cut_to_the_sub_window(ops, busy, kernel):
    """Each event is cut to its overlap with [mark, mark + window_s] and
    counted only where its middle lies inside; none outlasts the window."""
    cut = trace_reduce.sub_window(_marked(ops), 5.0)
    events = cut["devices"]["/device:TPU:0"]["XLA Ops"]
    assert all(2.0 <= s and s + d <= 7.0 for _, s, d, _ in events)
    assert trace_reduce.busy_seconds(cut) == busy
    assert trace_reduce.busy_seconds(_marked(ops)) > busy
    secs, n = trace_reduce.time_of(cut, "flash")
    assert (secs, n) == (pytest.approx(kernel[0]), kernel[1])
    share = readers.read(IDLE, {"trace": cut, "facts": {"window_s": 5.0}})
    assert 0.0 <= share <= 100.0 and share == pytest.approx(100.0 - 20.0 * busy)


def test_a_trace_without_the_mark_is_left_as_it_is():
    assert trace_reduce.sub_window(TRACE, 1.0) is TRACE
    assert trace_reduce.busy_seconds(TRACE) == pytest.approx(3.0)
    assert trace_reduce.time_of(TRACE, "flash") == (pytest.approx(2.0), 2)


def test_ranking_and_gaps_see_only_the_sub_window():
    """TRACE's ops over [1.2, 4.2]: the last flash and the first fusion
    fall outside, the first flash is cut to 0.3 s and, its middle
    outside, is not counted; one gap is left, and one host span goes."""
    trace = dict(TRACE, host_spans=sorted(
        TRACE["host_spans"] + [(trace_reduce.WINDOW_MARK, 1.2, 1e-6),
                               ("bench/late", 8.0, 1.0)], key=lambda e: e[1]))
    cut = trace_reduce.sub_window(trace, 3.0)
    assert [n for n, *_ in cut["host_spans"]] == [
        "bench/fit", trace_reduce.WINDOW_MARK, "bench/poll_sleep"]
    ranked = trace_reduce.top_ops(cut)
    assert [n for n, _ in ranked] == ["fusion", "flash_kernel"]
    assert [s for _, s in ranked] == [pytest.approx(0.5), pytest.approx(0.3)]
    assert trace_reduce.idle_gaps(cut) == [["bench/poll_sleep", 1.5]]
    assert trace_reduce.time_of(cut, "flash") == (None, 0)
    assert trace_reduce.time_of(cut, "scan_fn", "XLA Modules") == (None, 0)
    assert trace_reduce.time_of(cut, "admit", "XLA Modules") == (0.5, 1)


class _Handle:
    """A request that lands one token a poll."""

    def __init__(self, n_new):
        self.n_new, self.emitted = n_new, 0

    def done(self):
        self.emitted = min(self.n_new, self.emitted + 1)
        return self.emitted == self.n_new

    def result(self, timeout=None):
        return np.zeros(self.n_new, np.int32)


@pytest.mark.parametrize("traced", [True, False], ids=["traced", "untraced"])
def test_the_serving_loop_snapshots_at_its_trace_marks(monkeypatch, traced):
    """A traced loop opens a host span at each of 'trace0' and 'trace1'
    around the moment it takes, then snapshots the registry, all between
    the profiler's start and stop; an untraced one does neither."""
    import jax
    log = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name, time.perf_counter()))

        def __exit__(self, *exc):
            log.append(("exit", self.name, time.perf_counter()))

    class Tracer:
        def start(self):
            log.append(("start",))

        def stop(self):
            log.append(("stop",))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(drivers, "registry_snapshot",
                        lambda: log.append(("snapshot",)) or {"at": len(log)})
    srv = type("Srv", (), {"submit_async": lambda self, p, n_new: _Handle(n_new)})()
    requests = [[(np.zeros(3, np.int32), 4)] for _ in range(2)]
    _, _, times, snaps = drivers.closed_loop(
        srv, requests, 0.05, 0.4, 0.1, Tracer() if traced else None,
        lambda: None, poll_s=1e-3, settle_s=0.01)
    kept = [e for e in log if e[0] in ("start", "stop", "snapshot")
            or "trace" in e[1]]
    if not traced:
        assert kept == [] and snaps == {} and "trace0" not in times
        return
    assert [e[:2] for e in kept] == [
        ("start",), ("enter", "bench/trace0"), ("exit", "bench/trace0"),
        ("snapshot",), ("enter", "bench/trace1"), ("exit", "bench/trace1"),
        ("snapshot",), ("stop",)]
    for mark in ("trace0", "trace1"):
        (enter,), (leave,) = ([e[2] for e in log if e[:2] == (side, "bench/" + mark)]
                              for side in ("enter", "exit"))
        assert enter <= times[mark] <= leave
        # the snapshot taken right after that span
        assert log[snaps[mark]["at"] - 2][:2] == ("exit", "bench/" + mark)
    assert times["open"] < times["trace0"] < times["trace1"] < times["close"]


def test_costs_against_hand_counts():
    mm = 2 * (4 * 8 * 8 + 2 * 8 * 16)                      # 1024
    assert costs.matmul_params(SHAPE) == mm
    assert costs.train_flops_per_token(SHAPE, 4) == 6 * mm + 12 * 2 * 4 * 8
    # three tokens at contexts 5, 6, 7, LM head on two of them
    assert costs.lm_forward_flops(SHAPE, 5, 7, 2) == (
        2 * mm * 3 + 4 * 2 * 8 * 18 + 2 * 8 * 50 * 2)
    assert costs.lm_forward_flops(SHAPE, 5, 4, 0) == 0.0
    fwd = costs.flash_cost(SHAPE, 3, 4, False, False)
    assert fwd == {"flops": 2 * 2.0 * 3 * 4 * 4 * 8, "bytes": 4.0 * 3 * 4 * 8 * 2}
    bwd = costs.flash_cost(SHAPE, 3, 4, True, True)
    assert bwd == {"flops": fwd["flops"], "bytes": 2 * fwd["bytes"]}
    paged = costs.paged_attention_cost(SHAPE, 10.0)
    assert paged == {"flops": 4.0 * 2 * 8 * 10, "bytes": 2.0 * 2 * 8 * 2 * 10}
    assert costs.roofline_seconds(paged, PEAK) == (64.0, "bandwidth")
    assert costs.roofline_seconds({"flops": 1000.0, "bytes": 1.0}, PEAK)[1] == "compute"


def test_a_stall_in_the_window_moves_the_tail_and_the_rate():
    steady = [0.010] * 95 + [0.012] * 5
    stalled = [0.010] * 90 + [0.500] * 10      # one stall caught ten requests
    assert run.percentile(steady, 95) == pytest.approx(0.0101, abs=1e-4)
    assert run.percentile(stalled, 95) == pytest.approx(0.5)
    assert run.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.5
    # a failed request never met any limit: past 5% of them the tail is lost
    assert run.percentile([0.01] * 90 + [float("inf")] * 10, 95) == float("inf")
    facts = {"tokens": 1000.0, "window_s": 10.0, "ttft_s": stalled, "tpot_s": steady}
    same_work_with_stall = dict(facts, window_s=12.5)
    # a cell reports the end-to-end metrics the manifest lists for it,
    # each worked out from its name
    listed = {"end_to_end": [
        {"name": "setup_s", "unit": "s"},
        {"name": "serve_tokens_per_s", "unit": "tokens/s", "workloads": ["s"]},
        {"name": "ttft_p95_ms", "unit": "ms", "workloads": ["s"]},
        {"name": "ttft_p50_ms", "unit": "ms", "workloads": ["s"]},
        {"name": "tpot_p95_ms", "unit": "ms", "workloads": ["s"]},
        {"name": "train_tokens_per_s", "unit": "tokens/s", "workloads": ["t"]}]}
    a = run.end_to_end(listed, "s", facts, 3.0)
    b = run.end_to_end(listed, "s", same_work_with_stall, 3.0)
    assert set(a) == {"setup_s", "serve_tokens_per_s", "ttft_p95_ms",
                      "ttft_p50_ms", "tpot_p95_ms"}
    assert a["serve_tokens_per_s"] == (100.0, "tokens/s")
    assert b["serve_tokens_per_s"] == (80.0, "tokens/s")
    assert a["ttft_p95_ms"][0] == pytest.approx(500.0) and a["setup_s"] == (3.0, "s")
    assert a["ttft_p50_ms"][0] == pytest.approx(10.0)
    assert a["tpot_p95_ms"][0] == pytest.approx(10.1, abs=0.1)
    assert run.end_to_end(listed, "t", {"tokens": 8192 * 5, "window_s": 2.0}, 1.0) == {
        "setup_s": (1.0, "s"), "train_tokens_per_s": (20480.0, "tokens/s")}
    with pytest.raises(SystemExit, match="no arithmetic"):
        run.end_to_end({"end_to_end": [{"name": "goodput", "unit": "%"}]}, "s", facts, 1.0)
    # the tail beside a median that is end to end: a per-layer reader
    tail = {"reader": {"kind": "fact_percentile", "args": {
        "of": "ttft_s", "q": 95, "scale": 1000.0}}}
    assert readers.read(tail, {"facts": {"ttft_s": stalled}}) == pytest.approx(500.0)
    assert readers.read(tail, {"facts": {"ttft_s": []}}) is None
    assert readers.read(tail, {"facts": {}}) is None


@pytest.mark.parametrize("name,of", [("ttft_p95_ms.closed", "ttft_s"),
                                     ("tpot_p95_ms.closed", "tpot_s")])
def test_a_tail_read_per_layer_is_the_percentile_of_its_sub_window_list(name, of):
    """The per-layer tails take the 95th percentile of the list the
    serving loop keeps for the traced sub-window, in ms, and are silent
    where that list is empty or missing; each is reported only in a cell
    that does not bound it end to end."""
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"]["args"]["of"] == of
    stalled = [0.010] * 90 + [0.500] * 10
    assert readers.read(spec, {"facts": {of: stalled}}) == pytest.approx(500.0)
    assert readers.read(spec, {"facts": {of: []}}) is None
    assert readers.read(spec, {"facts": {}}) is None
    b = _manifest()
    cells = next(m for m in b["per_layer"] if m["name"] == name)["workloads"]
    same = [m for m in b["end_to_end"] if m["name"] == name.split(".")[0]]
    assert cells and not any(set(cells) & set(m["workloads"]) for m in same)


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_per_layer_reader_reads_made_up_facts():
    """Each metric's file resolves to a reduction; on counters that did
    not move or a pattern that matches nothing it returns None, never 0."""
    snap = lambda ticks, scans, occ, wait: {
        "counters": {"generation_server_ticks_total": ticks,
                     'generation_server_scan_ticks_total{k="8"}': scans,
                     'generation_server_scan_ticks_total{k="1"}': scans},
        "histograms": {"generation_server_slot_occupancy": {"sum": occ, "count": 2 * scans},
                       "train_data_wait_seconds": {"sum": wait, "count": 5}}}
    ctx = {"facts": {"window_s": 10.0, "steps": 4, "tokens": 100.0, "flops": 250.0,
                     "ctx_sum": 10.0},
           "before": snap(0.0, 0.0, 0.0, 0.0), "after": snap(90.0, 5.0, 9.0, 0.5),
           "trace": TRACE, "peak": PEAK, "family": post_ln, "shape": SHAPE,
           "traffic": {"batch": 3, "seq": 4}}
    got = {}
    for m in _manifest()["per_layer"]:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert (spec["layer"], spec["unit"], spec["moves"]) == (
            m["layer"], m["unit"], m["moves"])
        got[m["name"]] = readers.read(spec, ctx)
    assert got["data_wait_share"] == pytest.approx(5.0)
    assert got["train_mfu"] == got["serve_mfu"] == pytest.approx(25.0)
    assert got["train_step_device_ms"] == pytest.approx(750.0)
    assert got["device_idle_share.train"] == pytest.approx(70.0)
    assert got["scan_len_mean"] == pytest.approx(9.0)
    assert got["slot_occupancy"] == pytest.approx(90.0)
    # the hand-built trace names no real kernel: silent, not 0
    assert got["paged_attention_roofline"] is None
    assert got["flash_forward_roofline"] is None
    spec = {"reader": {"kind": "roofline_of", "args": {
        "pattern": "flash", "cost": "paged_attention"}}}
    assert readers.read(spec, ctx) == pytest.approx(100.0 * 64.0 / 2.0)
    # 1.5 s of scan programs over (2 "flash" events / 2 layers) = 1 tick
    assert readers.read({"reader": {"kind": "trace_time_of", "args": {
        "pattern": "scan_fn", "line": "XLA Modules", "scale": 1000.0,
        "per_events_of": {"pattern": "flash", "each": "layers"}}}}, ctx) == 1500.0
    assert got["decode_scan_tick_device_ms"] is None
    assert readers.read({"reader": {"kind": "registry_delta", "args": {
        "of": {"series": "absent_total"}}}}, ctx) is None


def test_manifest_names_units_and_files():
    b = _manifest()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    cells = {w["name"] for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    for w in b["workloads"]:
        assert name.match(w["name"]) and name.match(w["traffic"]) and len(w["why"]) <= 200
        assert w["name"] == f'{w["config"]}.{w["traffic"]}' and w["chips"] in (1, 4)
        with open(os.path.join(ROOT, "benchmark", "workloads", w["name"] + ".json")) as f:
            cell = json.load(f)
        assert cell["config"] == w["config"] and cell["driver"] in ("train", "serve_closed")
        assert os.path.isfile(os.path.join(ROOT, configs[w["config"]]["file"]))
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1 for m in e2e.values())
    for m in b["end_to_end"] + b["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher") and set(m.get("workloads", [])) <= cells
    for m in b["per_layer"]:
        # every cell that reports the metric reports the end-to-end one it moves
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads", cells))
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics",
                                           m["name"] + ".json"))
    for cell in cells:
        assert any("mfu" in m["name"] and cell in m["workloads"] for m in b["per_layer"])
