"""The eight per-layer metric files that read the program's own names
and counters (ISSUE 25), each on a hand-built trace and two made-up
registry snapshots: the hand-computed value where the name or series
is there, None -- never 0 -- where it is not."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import readers, trace_reduce  # noqa: E402
from benchmark.families import post_ln  # noqa: E402

SHAPE = {"d": 8, "layers": 2, "heads": 2, "ff": 16, "vocab": 50,
         "max_len": 32, "n_out": 50}
PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
CALL = " = (bf16[3,4,8]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[3,4,8]{2,1,0} %p.1)"

# one train step of two layers (a forward call and the backward's two
# kernels per layer), then two decode ticks of two layers and one admit
OPS = [("%flash_fwd.3" + CALL, 0.0, 1.0), ("%flash_fwd.4" + CALL, 1.0, 1.0),
       ("%flash_bwd_dkv.1" + CALL, 2.0, 2.0), ("%flash_bwd_dq.2" + CALL, 4.0, 1.0),
       ("%flash_bwd_dkv.5" + CALL, 5.0, 2.0), ("%flash_bwd_dq.6" + CALL, 7.0, 1.0),
       ("%fusion.9 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %flash_fwd.3)", 8.0, 1.0),
       ("%paged_attention.7" + CALL, 10.0, 0.5), ("%paged_attention.8" + CALL, 11.0, 0.5),
       ("%paged_attention.7" + CALL, 12.0, 0.5), ("%paged_attention.8" + CALL, 13.0, 0.5)]
MODULES = [("jit_train_step(7)", 0.0, 9.0), ("jit_decode_scan(11)", 10.0, 4.0),
           ("jit_admit_miss(12)", 15.0, 0.5), ("jit_admit_hit(13)", 16.0, 0.25)]
TRACE = {"devices": {"/device:TPU:0": {"XLA Ops": OPS, "XLA Modules": MODULES}},
         "host_spans": []}
# what the parent's program shows in the same places: JAX's own names
OLD_TRACE = {"devices": {"/device:TPU:0": {
    "XLA Ops": [("%jvp__.3" + CALL, 0.0, 1.0), ("%transpose_jvp___.1" + CALL, 2.0, 2.0),
                ("%closed_call.7" + CALL, 10.0, 0.5)],
    "XLA Modules": [("jit__step_impl(7)", 0.0, 9.0), ("jit_scan_fn(11)", 10.0, 4.0),
                    ("jit_admit(12)", 15.0, 0.5)]}}, "host_spans": []}

QUEUE = 'fleet_request_phase_seconds{phase="queue"}'
HOST = "generation_server_sched_host_seconds_total"


def snap(emitted, slot_ticks, admitted, admit_s, retire_s, q_sum, q_count):
    return {"counters": {"generation_server_tokens_emitted_total": emitted,
                         "generation_server_slot_ticks_total": slot_ticks,
                         "generation_server_admitted_total": admitted,
                         HOST + '{phase="admit"}': admit_s,
                         HOST + '{phase="retire"}': retire_s},
            "histograms": {QUEUE: {"sum": q_sum, "count": q_count}}}


EMPTY = {"counters": {}, "histograms": {}}
BEFORE = snap(100.0, 120.0, 4.0, 1.0, 2.0, 3.0, 4.0)
AFTER = snap(188.0, 220.0, 7.0, 1.25, 2.25, 4.5, 7.0)


def ctx_of(trace, before, after):
    return {"facts": {"window_s": 10.0, "steps": 1, "tokens": 100.0, "flops": 250.0,
                      "ctx_sum": 10.0},
            "before": before, "after": after, "trace": trace, "peak": PEAK,
            "family": post_ln, "shape": SHAPE,
            "traffic": {"batch": 3, "seq": 4}}


def metric(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
        return json.load(f)


# flash at (batch 3, seq 4, 2 heads x 4): 768 operations and 768 bytes a
# forward call, so bandwidth binds at 76.8 s; backward reads twice the
# bytes.  Paged: 2 * 2 bytes * 2 layers * 8 wide * 10 tokens of context
# = 640 bytes = 64 s.
HAND = {
    "flash_forward_roofline": 100.0 * (2 * 76.8) / 2.0,
    "flash_backward_roofline": 100.0 * (2 * 153.6) / 6.0,
    "paged_attention_roofline": 100.0 * 64.0 / 2.0,
    "decode_scan_tick_device_ms": 1000.0 * 4.0 / (4 / 2),
    "admit_device_ms": 1000.0 * 0.75 / 3.0,
    "queue_wait_mean_ms": 1000.0 * 1.5 / 3.0,
    "slot_tick_useful_share": 100.0 * 88.0 / 100.0,
    "sched_host_share": 100.0 * 0.5 / 10.0,
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_named_metric_reads_its_hand_computed_value(name):
    assert readers.read(metric(name), ctx_of(TRACE, BEFORE, AFTER)) == \
        pytest.approx(HAND[name])


@pytest.mark.parametrize("name", sorted(HAND))
def test_named_metric_is_silent_without_its_name_or_series(name):
    """On the parent's trace (JAX's positional names) and a registry
    without the new series every one of the eight reads None, not 0."""
    assert readers.read(metric(name), ctx_of(OLD_TRACE, EMPTY, EMPTY)) is None


def test_a_fusion_that_only_consumes_a_kernel_is_not_the_kernel():
    """``%fusion.9 = ... fusion(... %flash_fwd.3)`` names the kernel as
    an operand: the pattern is anchored at the instruction's own name."""
    pattern = metric("flash_forward_roofline")["reader"]["args"]["pattern"]
    assert trace_reduce.time_of(TRACE, pattern) == (pytest.approx(2.0), 2)


@pytest.mark.parametrize("name, args", [
    ("paged_attention_roofline", lambda a: a),
    ("decode_scan_tick_device_ms", lambda a: a["per_events_of"])])
def test_the_decode_kernel_is_found_by_its_own_name_alone(name, args):
    """The decode kernel returns three results, and the 160 characters
    the benchmark keeps of its HLO line end before ``custom-call(``:
    the two patterns that find it anchor on ``%paged_attention = ``."""
    line = ("%paged_attention.12 = (bf16[1024,64]{1,0:T(8,128)(2,1)}, "
            "bf16[786816,16,128]{2,1,0:T(8,128)(2,1)}, bf16[786816,16,128]"
            "{2,1,0:T(8,128)(2,1)}, f32[64,16,128]{2,1,0:T(8,128)}) "
            "custom-call(bf16[1024,64]{1,0} %fusion.1)")       # a fourth result
    cut = line[:trace_reduce.NAME_CHARS]
    assert "custom-call(" not in cut
    trace = {"devices": {"/device:TPU:0": {"XLA Ops": [(cut, 0.0, 0.5)]}},
             "host_spans": []}
    pattern = args(metric(name)["reader"]["args"])["pattern"]
    assert trace_reduce.time_of(trace, pattern) == (0.5, 1)
    assert trace_reduce.time_of(TRACE, pattern) == (pytest.approx(2.0), 4)


def test_manifest_entries_of_the_named_metrics():
    """All eight are in BENCHMARK.json, which alone says which cells
    report a metric: a metric's file carries no ``workloads``.  The
    four that matched JAX's positional names are gone from both."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    trace_read = {"flash_forward_roofline", "flash_backward_roofline",
                  "paged_attention_roofline", "decode_scan_tick_device_ms",
                  "admit_device_ms"}
    assert per_layer["ttft_p95_ms.closed"]["source"] == "host_clock"
    for name in HAND:
        m, spec = per_layer[name], metric(name)
        assert m["source"] == ("device_trace" if name in trace_read
                               else "program_counter")
        assert (m["layer"], m["unit"], m["moves"]) == (
            spec["layer"], spec["unit"], spec["moves"])
        assert "workloads" not in spec and "not_in_manifest" not in spec
    for name in ("flash_fwd_roofline", "flash_bwd_roofline",
                 "paged_attn_roofline", "decode_tick_device_ms"):
        assert name not in per_layer
        assert not os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
