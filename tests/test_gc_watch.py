"""Python's collections as counters and a profiler host span
(``telemetry.gc_watch``): what a collection moves, that the hook takes
no lock, and that a generation >= 1 collection lands in a running
capture as ``gc/collect``."""
import gc
import glob
import os
import threading

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.telemetry import gc_watch

SECONDS = 'python_gc_seconds_total{generation="%d"}'
COUNT = 'python_gc_collections_total{generation="%d"}'


def test_a_full_collection_moves_generation_2_counters():
    before = telemetry.get_registry().snapshot()["counters"]
    gc.collect()
    after = telemetry.get_registry().snapshot()["counters"]
    assert after[COUNT % 2] - before[COUNT % 2] >= 1
    assert after[SECONDS % 2] > before[SECONDS % 2]
    assert gc_watch.seconds_total() >= after[SECONDS % 2]


def test_every_generation_is_a_series_before_any_collection():
    """Every child exists from the start: a reader of the family finds
    it in a process that has not collected yet, and reads 0 there."""
    names = set(telemetry.get_registry().snapshot()["counters"])
    assert {f % g for f in (SECONDS, COUNT) for g in gc_watch.GENERATIONS} \
        <= names


def test_the_scrape_brings_the_counters_up_to_date():
    reg = telemetry.get_registry()
    n = reg.get("python_gc_collections_total").labels(generation="2")
    before = n.value
    gc.collect()
    reg.render_prometheus()
    assert n.value >= before + 1


def test_a_collection_under_the_locks_it_could_meet_returns():
    """The callback runs at any allocation, inside ``with self._lock:``
    of a registry child or of the tracer -- non-reentrant locks, so a
    callback that took one would hang its own thread."""
    reg = telemetry.get_registry()
    child = reg.get("python_gc_seconds_total").labels(generation="2")
    tracer = telemetry.get_tracer()
    done = threading.Event()

    def collect_under_locks():
        with child._lock, tracer._lock:
            gc.collect()
            gc.collect(1)
        done.set()

    t = threading.Thread(target=collect_under_locks, daemon=True)
    t.start()
    t.join(timeout=30)
    assert done.is_set(), "a collection under the registry's and the " \
        "tracer's locks did not return"
    reg.snapshot()                 # and the locks were let go


def test_a_collection_of_generation_1_up_is_a_span_in_the_capture(tmp_path):
    """... and one of generation 0 is only counted."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        gc.collect(0)
        gc.collect()
    (pb,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    found = [dict(e.stats) for plane in ProfileData.from_file(pb).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == "gc/collect"]
    assert {"generation": 2} in found
    assert {"generation": 0} not in found


def test_threads_that_collect_and_read_at_once_lose_no_collection():
    """Many threads collecting and reading the registry at once, with
    the interpreter switching threads as often as it can: once all
    joined, the counters hold exactly what the callback counted."""
    import sys
    reg = telemetry.get_registry()
    counts = reg.get("python_gc_collections_total")
    read = lambda: [counts.labels(generation=g).value
                    for g in gc_watch.GENERATIONS]
    gc_watch.sync()
    before, counted = read(), list(gc_watch._count)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(i):
            for n in range(40):
                junk = [[j] for j in range(2000)]
                gc.collect(n % 3)
                if i % 4:
                    for _ in range(50):
                        gc_watch.sync()
                else:
                    reg.snapshot()
                del junk
        threads = [threading.Thread(target=work, args=(i,), daemon=True)
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    gc_watch.sync()
    moved = [a - b for a, b in zip(read(), before)]
    assert moved == [a - b for a, b in zip(gc_watch._count, counted)]
    assert sum(moved) >= 16      # a collect() that meets one running returns
