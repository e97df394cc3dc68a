"""The device's idle time put down to the scheduler's phases and to
Python's collections (``benchmark/idle_split.py``), and the six metric
files that read them, on a hand-built trace cut to its sub-window as
``run.py`` cuts it: the hand-computed value where the span or series is
there, None -- never 0 -- where it is not."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import idle_split, readers, trace_reduce  # noqa: E402

WINDOW = 10.0
AMONG = ("serve/", "gc/")
OPS = [("%fusion.1 = f32[8]{0} fusion()", 98.0, 1.5),    # before the window
       ("%fusion.2 = f32[8]{0} fusion()", 101.0, 2.0),
       ("%fusion.3 = f32[8]{0} fusion()", 104.0, 4.0),
       ("%fusion.4 = f32[8]{0} fusion()", 108.5, 1.0),
       ("%fusion.5 = f32[8]{0} fusion()", 111.0, 1.0)]   # after it
# device idle in [100, 110]: [100, 101], [103, 104], [108, 108.5] and
# [109.5, 110] -- 3 s of the 10, both edges included
SPANS = [("bench/trace0", 100.0, 0.0),
         ("serve/idle", 95.0, 5.0),                 # ends at the mark
         ("serve/admit", 100.0, 1.0),                # the leading edge: 1.0
         ("serve/tick", 101.0, 2.25),
         ("serve/launch", 101.0, 0.125),
         ("serve/poll", 101.125, 2.125),             # [103, 103.25]
         ("bench/submit", 102.875, 1.125),           # never takes an instant
         ("serve/retire", 103.25, 0.75),             # 0.75 less gc's 0.25
         ("gc/collect", 103.5, 0.25),                # the innermost
         ("serve/admit", 104.0, 4.25),               # [108, 108.25]
         ("serve/launch", 109.375, 1.125)]           # the trailing edge: 0.5
# and nothing kept in [108.25, 108.5]: 0.25 under "none"
TRACE = trace_reduce.sub_window(
    {"devices": {"/device:TPU:0": {"XLA Ops": OPS}},
     "host_spans": sorted(SPANS, key=lambda s: s[1])}, WINDOW)
PARTS = {"serve/admit": 1.25, "serve/launch": 0.5, "serve/poll": 0.25,
         "serve/retire": 0.5, "gc/collect": 0.25, "none": 0.25}
GC = 'python_gc_seconds_total{generation="%d"}'
GC_N = 'python_gc_collections_total{generation="%d"}'
BEFORE = {"counters": {GC % 0: 0.01, GC % 2: 0.1, GC_N % 0: 10.0}}
AFTER = {"counters": {GC % 0: 0.03, GC % 2: 0.2, GC_N % 0: 12.0}}
EMPTY = {"counters": {}}
HAND = {"idle_share.admit": 12.5, "idle_share.launch": 5.0,
        "idle_share.poll": 2.5, "idle_share.retire": 5.0,
        "idle_share.gc": 2.5, "gc_share": 100.0 * 0.12 / WINDOW}


def ctx_of(trace, before, after):
    return {"facts": {"window_s": WINDOW}, "before": before, "after": after,
            "trace": trace}


def metric(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
        return json.load(f)


def read(name, ctx):
    """The metric file's reader: ``readers.READERS``' own, or this
    module's until ``READERS`` holds it."""
    spec = metric(name)["reader"]
    fn = readers.READERS.get(spec["kind"]) or getattr(idle_split, spec["kind"])
    return fn(ctx, spec.get("args", {}))


def test_every_idle_instant_goes_to_the_innermost_open_span():
    parts = idle_split.idle_under(TRACE, WINDOW, AMONG)
    assert parts == PARTS
    assert "bench/submit" not in parts and "serve/tick" not in parts


def test_the_parts_sum_to_the_idle_share_with_both_edges():
    parts = idle_split.idle_under(TRACE, WINDOW, AMONG)
    busy = trace_reduce.busy_seconds(TRACE)
    assert busy == pytest.approx(7.0)
    assert 100.0 * sum(parts.values()) / WINDOW == pytest.approx(
        100.0 - 100.0 * busy / WINDOW)
    # [100, 101] under admit and [109.5, 110] under launch
    assert parts["serve/admit"] >= 1.0 and parts["serve/launch"] == 0.5


def test_a_collection_inside_a_phase_takes_the_instant():
    """... and a bench/ span, however short, never does."""
    spans = [("bench/trace0", 0.0, 0.0), ("serve/retire", 0.0, 4.0),
             ("gc/collect", 1.0, 1.0),
             ("bench/result", 1.5, 0.1)]
    trace = {"devices": {"d": {"XLA Ops": [("op", 3.0, 1.0)]}},
             "host_spans": spans}
    assert idle_split.idle_under(trace, 4.0, AMONG) == pytest.approx(
        {"serve/retire": 2.0, "gc/collect": 1.0})


def test_two_devices_average():
    ops = {"/device:TPU:0": {"XLA Ops": [("op", 0.0, 1.0)]},
           "/device:TPU:1": {"XLA Ops": [("op", 0.0, 2.0)]}}
    trace = {"devices": ops, "host_spans": [("bench/trace0", 0.0, 0.0),
                                            ("serve/poll", 0.0, 4.0)]}
    parts = idle_split.idle_under(trace, 4.0, AMONG)
    assert parts == pytest.approx({"serve/poll": 2.5})
    assert sum(parts.values()) == pytest.approx(4.0 - trace_reduce.busy_seconds(trace))


@pytest.mark.parametrize("name", sorted(HAND))
def test_new_metric_reads_its_hand_computed_value(name):
    assert read(name, ctx_of(TRACE, BEFORE, AFTER)) == pytest.approx(HAND[name])


@pytest.mark.parametrize("name", sorted(HAND))
def test_new_metric_is_silent_on_the_parents_program(name):
    """The parent opens no gc/collect span and counts no collection;
    its scheduler spans are named as here, so that its trace is cut to
    the four phases it has: the gc readings are None, not 0."""
    parent = dict(TRACE, host_spans=[s for s in TRACE["host_spans"]
                                     if s[0] != "gc/collect"])
    value = read(name, ctx_of(parent, EMPTY, EMPTY))
    if name in ("idle_share.gc", "gc_share"):
        assert value is None
    else:
        assert value is not None


@pytest.mark.parametrize("name", ["idle_share.%s" % p for p in
                                  ("admit", "launch", "poll", "retire")])
def test_a_phase_absent_from_the_trace_reads_none(name):
    trace = dict(TRACE, host_spans=[s for s in TRACE["host_spans"]
                                    if not s[0].startswith("serve/")])
    assert read(name, ctx_of(trace, BEFORE, AFTER)) is None


def test_no_collection_in_the_sub_window_of_a_program_that_counts_them():
    """A program that counts collections would have opened the span:
    none in the sub-window reads 0, and the scheduler's shares take the
    instant the collection held."""
    trace = dict(TRACE, host_spans=[s for s in TRACE["host_spans"]
                                    if s[0] != "gc/collect"])
    ctx = ctx_of(trace, BEFORE, AFTER)
    assert read("idle_share.gc", ctx) == 0.0
    assert read("idle_share.retire", ctx) == pytest.approx(7.5)


def test_the_new_files_and_the_manifest():
    """Each file names its layer and what it moves; ``gc_share`` reads
    through an accepted reader and is in the manifest for the four
    serving cells.  The five ``idle_share.*`` read through
    ``idle_split.trace_idle_under``, which ``readers.READERS`` does not
    hold yet: a manifest entry for them would fail every traced run."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    serving = next(m for m in manifest["end_to_end"]
                   if m["name"] == "serve_tokens_per_s")["workloads"]
    for name in HAND:
        spec = metric(name)
        assert spec["name"] == name and spec["unit"] == "%"
        assert spec["moves"] == "serve_tokens_per_s"
        assert spec["layer"] == ("host runtime" if "gc" in name else "scheduler")
        wired = spec["reader"]["kind"] in readers.READERS
        assert (name in per_layer) == wired
    gc_share = per_layer["gc_share"]
    assert (gc_share["source"], gc_share["layer"], gc_share["better"]) == \
        ("program_counter", "host runtime", "lower")
    assert gc_share["workloads"] == serving
