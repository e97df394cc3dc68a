"""Telemetry subsystem: registry semantics, thread safety under
hammering, serving instrumentation against a live ParallelInference,
the scrape endpoint, span tracing, the report bridge, and the CI smoke
script (ISSUE 1 acceptance: >= 20 healthy series from one train+serve
run)."""
import json
import math
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import (MultiLayerNetwork, NeuralNetConfiguration,
                                telemetry)
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterator import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.layers_core import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.updaters import Adam
from deeplearning4j_tpu.parallel import ParallelInference
from deeplearning4j_tpu.telemetry import MetricsRegistry, SpanTracer
from deeplearning4j_tpu.ui import InMemoryStatsStorage, render_report


def _model(seed=3):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=1e-2)).list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=4, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------
def test_registry_counter_gauge_histogram():
    r = MetricsRegistry()
    c = r.counter("req_total", "requests", labelnames=("path",))
    c.labels(path="flash").inc()
    c.labels(path="flash").inc(2)
    c.labels(path="xla").inc()
    g = r.gauge("depth", "queue depth")
    g.set(5)
    g.dec(2)
    h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.7, 20.0):
        h.observe(v)
    assert c.labels(path="flash").value == 3
    assert g.value == 3
    assert h.count == 4 and h.sum == pytest.approx(21.25)
    txt = r.render_prometheus()
    assert 'req_total{path="flash"} 3.0' in txt
    assert '# TYPE lat_seconds histogram' in txt
    assert 'lat_seconds_bucket{le="+Inf"} 4' in txt
    assert "lat_seconds_count 4" in txt
    # get-or-create is idempotent; kind mismatch is an error
    assert r.counter("req_total", labelnames=("path",)) is c
    with pytest.raises(ValueError):
        r.gauge("req_total")
    with pytest.raises(ValueError):
        r.counter("req_total", labelnames=("other",))
    # re-registering a histogram with different buckets would silently
    # mis-shape its quantiles — must raise, not return the old family
    with pytest.raises(ValueError):
        r.histogram("lat_seconds", buckets=(5.0,))
    # counters only go up
    with pytest.raises(ValueError):
        c.labels(path="xla").inc(-1)


def test_histogram_percentiles_derivable():
    r = MetricsRegistry()
    h = r.histogram("h", buckets=tuple((i + 1) / 10 for i in range(10)))
    for v in np.linspace(0.01, 0.99, 100):
        h.observe(float(v))
    assert math.isnan(r.histogram("empty", buckets=(1,)).percentile(0.5))
    assert 0.4 < h.percentile(0.50) < 0.6
    assert 0.9 < h.percentile(0.95) <= 1.0
    assert h.percentile(0.99) <= 1.0


def test_snapshot_merge_aggregates_workers():
    """Driver-side aggregation: counters/histogram series ADD across
    worker snapshots; gauges take the incoming value."""
    w = MetricsRegistry()
    w.counter("steps_total", labelnames=("worker",)).labels(
        worker="0").inc(5)
    w.gauge("mfu").set(0.4)
    w.histogram("lat", buckets=(1.0,)).observe(0.5)
    # label values containing ','/'='/'"' must survive the series
    # round-trip (a mesh-shape label is exactly this string shape)
    mesh = '{"data": 2, "model": 2}'
    w.counter("meshes_total", labelnames=("mesh",)).labels(
        mesh=mesh).inc(3)
    snap = json.loads(json.dumps(w.snapshot()))  # jsonl round-trip
    driver = MetricsRegistry()
    driver.merge_snapshot(snap)
    driver.merge_snapshot(snap)
    assert driver.get("steps_total").labels(worker="0").value == 10
    assert driver.get("mfu").value == pytest.approx(0.4)
    assert driver.get("lat").count == 2
    assert driver.get("lat").sum == pytest.approx(1.0)
    assert driver.get("meshes_total").labels(mesh=mesh).value == 6


def test_thread_safety_hammer():
    """8 threads x 2500 ops on ONE counter and ONE histogram — exact
    totals prove the per-child locks close the lost-update race a bare
    float += has."""
    r = MetricsRegistry()
    c = r.counter("hits_total")
    h = r.histogram("obs_seconds", buckets=(0.5, 1.0))
    n_threads, n_ops = 8, 2500

    def hammer(tid):
        for i in range(n_ops):
            c.inc()
            h.observe((tid + i) % 2)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_ops
    assert h.count == n_threads * n_ops
    uppers, counts, total, count = h._default().state()
    assert sum(counts) == count == n_threads * n_ops


# ---------------------------------------------------------------------------
# Serving telemetry against a live ParallelInference
# ---------------------------------------------------------------------------
def test_serving_telemetry_concurrent_clients(rng):
    reg = telemetry.get_registry()
    lat = reg.get("inference_latency_seconds")
    occ = reg.get("inference_batch_occupancy")
    reqs = reg.get("inference_requests_total")
    before_lat, before_occ = lat.count, occ.count
    before_reqs = reqs.value
    n_clients = 24
    xs = [rng.normal(size=(8,)).astype(np.float32)
          for _ in range(n_clients)]
    model = _model()
    with ParallelInference(model, batch_limit=8, timeout_ms=10) as pi:
        results = [None] * n_clients

        def call(i):
            results[i] = pi.output(xs[i])

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert all(r is not None for r in results)
    # latency histogram counts EQUAL completed requests
    assert lat.count - before_lat == n_clients
    assert reqs.value - before_reqs == n_clients
    assert not math.isnan(lat.sum)
    # queue-depth gauge returned to 0 after the drain
    assert reg.get("inference_queue_depth").value == 0
    # batch-occupancy buckets are populated
    assert occ.count - before_occ >= 1
    snap = reg.snapshot()
    h = snap["histograms"]["inference_batch_occupancy"]
    assert sum(h["buckets"].values()) + h["inf"] == h["count"] > 0


def test_serving_timeout_and_shed_counters(rng):
    reg = telemetry.get_registry()
    timeouts = reg.get("inference_timeout_total")
    shed = reg.get("inference_shed_total")
    t_before, s_before = timeouts.value, shed.value
    model = _model()
    pi = ParallelInference(model, batch_limit=1, queue_limit=1,
                           timeout_ms=5, shed_on_full=True)
    try:
        real = pi._apply
        pi._apply = lambda *a: (time.sleep(0.25), real(*a))[1]
        x = rng.normal(size=(8,)).astype(np.float32)
        # deadline shorter than the slowed forward -> caller times out
        with pytest.raises(TimeoutError):
            pi.output(x, timeout=0.02)
        assert timeouts.value - t_before == 1
        # worker busy with the slow request; fill the 1-slot queue,
        # then the next request sheds instead of blocking
        filler = threading.Thread(
            target=lambda: pi.output(x, timeout=2))
        filler.start()
        time.sleep(0.05)       # let the filler land in the queue
        with pytest.raises(RuntimeError, match="shed"):
            pi.output(x)
        assert shed.value - s_before == 1
        filler.join(timeout=5)
    finally:
        pi.shutdown()


# ---------------------------------------------------------------------------
# Train-side bridge, scrape endpoint, tracing, report
# ---------------------------------------------------------------------------
def _fit_with_listener(storage=None):
    from deeplearning4j_tpu.ui import StatsListener
    m = _model()
    listeners = [telemetry.TelemetryListener(
        storage=storage, flops_per_example=1000.0, peak_flops=1e12)]
    if storage is not None:  # iteration records interleave with snapshots
        listeners.append(StatsListener(storage))
    m.set_listeners(*listeners)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(96, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 96)]
    m.fit(ListDataSetIterator(DataSet(x, y).batch_by(32)), n_epochs=2)
    return m


def test_mfu_peak_comes_from_the_device_table_or_the_caller():
    """One peaks table keyed by device_kind: a kind it does not hold
    (this CPU) raises where a peak is needed and the caller passed
    none — never a default chip's number."""
    from deeplearning4j_tpu.runtime.backend import Backend, peak_flops
    assert peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no peak FLOP/s on record"):
        peak_flops()
    with pytest.raises(ValueError, match="no peak FLOP/s on record"):
        telemetry.TelemetryListener(flops_per_example=1000.0)
    # no MFU asked for, or the caller's own peak: no table lookup
    assert telemetry.TelemetryListener().peak_flops is None
    assert telemetry.TelemetryListener(
        flops_per_example=1000.0, peak_flops=1e12).peak_flops == 1e12
    assert Backend(platform="tpu", n_devices=1).is_tpu
    assert not Backend(platform="cpu", n_devices=1).is_tpu


def test_fit_loop_and_listener_metrics():
    reg = telemetry.get_registry()
    iters = reg.get("train_iterations_total")
    epochs = reg.get("train_epochs_total")
    wait = reg.get("train_data_wait_seconds")
    i0, e0, w0 = iters.value, epochs.value, wait.count
    storage = InMemoryStatsStorage()
    _fit_with_listener(storage)
    assert iters.value - i0 == 6          # 3 batches x 2 epochs
    assert epochs.value - e0 == 2
    assert wait.count - w0 == 6
    assert reg.get("train_loss").value > 0
    assert reg.get("mfu").value > 0       # flops_per_example was given
    snaps = [r for r in storage.records()
             if r.get("type") == "telemetry_snapshot"]
    assert len(snaps) == 2                # one per epoch
    assert "train_iterations_total" in snaps[-1]["counters"]


def test_scrape_endpoint_and_series_floor(rng):
    import jax.numpy as jnp
    from deeplearning4j_tpu import kernels
    q = jnp.asarray(rng.normal(size=(1, 2, 8, 4)), jnp.float32)
    kernels.attention(q, q, q)    # give flash_route_total a child
    reg = telemetry.get_registry()
    with telemetry.start_metrics_server(reg, port=0) as srv:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5
        ).read().decode()
        assert urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5).status == 200
    series = {ln.rsplit(" ", 1)[0] for ln in body.splitlines()
              if ln and not ln.startswith("#")}
    # the acceptance floor for the combined-run scrape
    assert len(series) >= 20, sorted(series)
    assert any(s.startswith("flash_route_total") for s in series)
    assert reg.series_count() >= len(series)


def test_span_tracer_nesting_and_export(tmp_path):
    tr = SpanTracer()
    with tr.span("outer", phase="fit"):
        with tr.span("inner"):
            pass
    with pytest.raises(KeyError):
        with tr.span("fails"):
            raise KeyError("boom")
    evs = tr.events()
    names = [e["name"] for e in evs]
    assert names == ["inner", "outer", "fails"]  # completion order
    outer = evs[1]
    inner = evs[0]
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert evs[2]["args"]["error"] == "KeyError"
    p = tr.export_jsonl(str(tmp_path / "trace.jsonl"))
    lines = [json.loads(l) for l in open(p) if l.strip()]
    assert {l["ph"] for l in lines} == {"X"}
    tr.export_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(tmp_path / "trace.json"))
    assert len(doc["traceEvents"]) == 3


def _profile(path):
    """The profiler as the benchmark runs it: host spans and device
    lines, no Python call stacks."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return jax.profiler.trace(str(path), profiler_options=opts)


def _host_span_names(path) -> set:
    import glob
    from jax.profiler import ProfileData
    (pb,) = glob.glob(os.path.join(str(path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return {e.name for plane in ProfileData.from_file(pb).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def test_scoped_span_lands_in_a_running_profile(tmp_path):
    """ISSUE 25: a ``with``-span is also a host span of the same name
    in a running jax.profiler capture (one clock with the device
    lines); a tracked ``begin()`` span, which may end on another
    thread, is not.  The jsonl export holds both, as before."""
    tr = SpanTracer()
    with _profile(tmp_path / "prof"):
        with tr.span("x/y", k=1) as sp:
            sp.note(n=3)
            tracked = tr.begin("x/tracked", trace="r-1")
        ender = threading.Thread(target=tracked.end)
        ender.start()
        ender.join(timeout=10)
    names = _host_span_names(tmp_path / "prof")
    assert "x/y" in names and "x/tracked" not in names
    by_name = {e["name"]: e for e in tr.events()}
    assert by_name["x/y"]["args"] == {"k": 1, "n": 3}
    assert by_name["x/tracked"]["args"] == {"trace": "r-1"}
    assert tr.open_spans() == []


def test_scoped_span_without_a_profile_is_the_span_it_was(tmp_path):
    """No capture running: the same events, nothing written anywhere,
    and a disabled tracer still yields a span that takes notes."""
    tr = SpanTracer()
    with tr.span("outer", phase="fit") as sp:
        assert sp.name == "outer"
    (ev,) = tr.events()
    assert (ev["name"], ev["ph"], ev["args"]) == ("outer", "X",
                                                  {"phase": "fit"})
    assert os.listdir(tmp_path) == []
    off = SpanTracer(enabled=False)
    with off.span("quiet") as sp:
        sp.note(n=1)
    assert off.events() == [] and sp.args == {}


def test_report_embeds_telemetry_and_trace_link(tmp_path):
    storage = InMemoryStatsStorage()
    _fit_with_listener(storage)
    trace = telemetry.get_tracer().export_jsonl(
        str(tmp_path / "trace.jsonl"))
    assert os.path.getsize(trace) > 0     # fit spans were recorded
    out = render_report(storage, str(tmp_path / "report.html"),
                        trace_path="trace.jsonl")
    html = open(out).read()
    assert "Telemetry" in html
    assert "train_iterations_total" in html
    assert 'href="trace.jsonl"' in html
    assert "Loss" in html                 # iteration records still chart


def test_check_telemetry_smoke():
    """The CI smoke script end to end (5-iter train + 16-request serve
    + live scrape): exit code 0 inside the tier-1 budget."""
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "scripts", "check_telemetry.py")
    spec = importlib.util.spec_from_file_location("check_telemetry", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main() == 0
