"""The decode scheduler's phases partition its thread (``serve/idle``,
``admit``, ``launch``, ``poll``, ``retire``), ``serve/tick`` holds
exactly one ``launch`` and one ``poll``, and a gap of more than
``STALL_S`` between two landings of tokens is counted as a stall, by
the phase that held it."""
import time

import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.parallel import GenerationServer
from deeplearning4j_tpu.parallel import generation_server as gs
from deeplearning4j_tpu.resilience import FaultInjector
from deeplearning4j_tpu.zoo.gpt import Gpt

PHASES = {"serve/" + p for p in gs._SchedPhases.PHASES}
STALL = "generation_server_stall_seconds_total"


@pytest.fixture(scope="module")
def net():
    return Gpt(vocab_size=50, max_len=32, d_model=32, n_layers=2, n_heads=4,
               d_ff=64, seq_len=8, compute_dtype=None, seed=3).init_graph()


def _serve(net, requests, **kw):
    """Run ``requests`` (prompt, n_new) through a fresh server; the
    tracer's events its scheduler thread left."""
    tracer = telemetry.get_tracer()
    seq0 = max((ev["seq"] for ev in tracer.events()), default=-1)
    with GenerationServer(net, max_len=32, tick_timeout_s=None, **kw) as srv:
        handles = [srv.submit_async(np.asarray(p, np.int32), n)
                   for p, n in requests]
        for h in handles:
            h.result(timeout=300)
    events = [ev for ev in tracer.events() if ev["seq"] > seq0]
    (tid,) = {ev["tid"] for ev in events if ev["name"] == "serve/tick"}
    return [ev for ev in events if ev["tid"] == tid]


def test_the_phases_partition_the_scheduler_thread(net):
    """From the first admission to the thread's exit every instant lies
    in exactly one phase: each phase ends where the next one starts,
    whatever preempts the thread between the two."""
    events = _serve(net, [([1, 2, 3], 9), ([4, 5], 5), ([6, 7, 8, 9], 7)],
                    n_slots=2, tick_batch=2)
    phases = sorted((ev for ev in events if ev["name"] in PHASES),
                    key=lambda ev: ev["ts"])
    first = next(i for i, ev in enumerate(phases)
                 if ev["name"] == "serve/admit")
    phases = phases[first:]
    assert {ev["name"] for ev in phases} == PHASES
    for a, b in zip(phases, phases[1:]):
        hole = b["ts"] - (a["ts"] + a["dur"])
        assert abs(hole) < 1e-3, (a["name"], b["name"], hole)   # 1 ns


def test_a_tick_holds_exactly_one_launch_and_one_poll(net):
    events = _serve(net, [([1, 2, 3], 6), ([4, 5], 4)], n_slots=2,
                    tick_batch=4)
    ticks = [ev for ev in events if ev["name"] == "serve/tick"]
    assert ticks
    for tick in ticks:
        start, end = tick["ts"] - 1e-3, tick["ts"] + tick["dur"] + 1e-3
        inside = sorted(ev["name"] for ev in events
                        if ev["name"] in PHASES and start <= ev["ts"]
                        and ev["ts"] + ev["dur"] <= end)
        assert inside == ["serve/launch", "serve/poll"]
        assert set(tick["args"]) == {"active", "queued", "k", "spec"}
    # the work after the poll is retire's, outside the tick
    retires = [ev for ev in events if ev["name"] == "serve/retire"]
    assert len(retires) >= len(ticks)


def test_scheduler_host_seconds_keep_only_admit_and_retire(net):
    _serve(net, [([1, 2, 3], 4)], n_slots=1, tick_batch=2)
    family = telemetry.get_registry().get(
        "generation_server_sched_host_seconds_total")
    assert {lv for lv, _ in family._items()} == {("admit",), ("retire",)}


def test_a_gap_past_stall_s_is_one_stall_by_the_phase_that_held_it(net):
    """Single ticks, the fourth dispatch held 1.2 s at the chaos site
    inside ``launch``, the watchdog's deadline above it: one stall, and
    its seconds under launch.  A warm-up request first, so that no
    compile inside a dispatch passes ``STALL_S`` too."""
    reg = telemetry.get_registry()
    stalls = reg.counter("generation_server_stalls_total")
    split = lambda: {p: reg.counter(STALL, labelnames=("phase",)).labels(
        phase=p).value for p in (*gs._SchedPhases.PHASES, "gc")}
    flight = telemetry.get_flight_recorder()
    with GenerationServer(net, n_slots=1, max_len=32, tick_batch=1,
                          tick_timeout_s=10.0) as srv:
        prompt = np.asarray([1, 2, 3], np.int32)
        srv.submit(prompt, n_new=6, timeout=300)
        n0, s0 = stalls.value, split()
        seq0 = max((e["seq"] for e in flight.events()), default=-1)
        with FaultInjector(["serve_tick_stall@3:1.2"]):
            srv.submit(prompt, n_new=6, timeout=300)
    assert stalls.value - n0 == 1
    moved = {p: v - s0[p] for p, v in split().items()}
    assert moved["launch"] >= 1.2
    assert sum(v for p, v in moved.items() if p != "gc") < 1.2 + 0.5
    assert moved["idle"] == 0.0
    (ev,) = [e for e in flight.events()
             if e["kind"] == "stall" and e["seq"] > seq0]
    assert (ev["k"], ev["active"]) == (1, 1)
    assert ev["seconds"] >= ev["launch"] >= 1.2


def test_idle_time_between_requests_is_no_stall(net):
    """A gap that the scheduler spent blocked on an empty queue is not
    a stall, however long."""
    stalls = telemetry.get_registry().counter("generation_server_stalls_total")
    with GenerationServer(net, n_slots=1, max_len=32, tick_batch=1,
                          tick_timeout_s=None) as srv:
        prompt = np.asarray([1, 2, 3], np.int32)
        srv.submit(prompt, n_new=3, timeout=300)
        n0 = stalls.value
        time.sleep(gs.STALL_S + 0.2)
        srv.submit(prompt, n_new=3, timeout=300)
    assert stalls.value == n0
