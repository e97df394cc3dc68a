#!/usr/bin/env python3
"""Run ONE cell of BENCHMARK.json once:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that owns the chip.  It fails -- no CPU fallback, no result
line -- unless JAX finds a TPU with as many chips as the cell asks for.
It builds the cell's configuration from the seed, drives the first steps
or warms exactly the cell's shapes (set-up), measures one window, frees
the program's state, compares what the timed path produced with the
plain reference, and prints as the last line of stdout one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
(``breakdown`` when traced) and ``compared``.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.

A cell is data: ``workloads/<cell>.json`` (driver, traffic, limits),
the configuration file BENCHMARK.json names for it, and one file under
``layer_metrics/`` for each per-layer metric.  Whatever depends on the
architecture -- shape, weights, reference, costs -- comes from the
module the configuration's ``family`` names (``families/post_ln.py``
says what a family gives).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import json
import os
import re
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


from benchmark.readers import percentile  # noqa: E402


def load(*parts) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def cell_files(name: str) -> tuple:
    """(the manifest's entry, the cell's file, its configuration)."""
    manifest = load("BENCHMARK.json")
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    return manifest, load("benchmark", "workloads", name + ".json"), load(cfg["file"])


_TAIL = re.compile(r"^(ttft|tpot)_p(\d+)_ms$")


def end_to_end(manifest: dict, name: str, facts: dict, setup_s: float) -> dict:
    """The end-to-end metrics BENCHMARK.json lists for the cell, each
    by its name: ``setup_s``; ``*tokens_per_s``, all the window's
    tokens over all its seconds; ``ttft_p<q>_ms`` / ``tpot_p<q>_ms``,
    that percentile of ALL the window's requests."""
    out = {}
    for m in manifest["end_to_end"]:
        if name not in m.get("workloads", [name]):
            continue
        tail = _TAIL.match(m["name"])
        if m["name"] == "setup_s":
            value = setup_s
        elif m["name"].endswith("tokens_per_s"):
            value = facts["tokens"] / facts["window_s"]
        elif tail:
            value = 1e3 * percentile(facts[tail.group(1) + "_s"],
                                     int(tail.group(2)))
        else:
            raise SystemExit(f"run.py: no arithmetic for the end-to-end "
                             f"metric {m['name']!r}")
        out[m["name"]] = (value, m["unit"])
    return out


class Tracer:
    """The profiler over a sub-window, host spans and device lines only
    (no Python call stacks: they would swamp the trace)."""

    def __init__(self, path):
        self.path = path
        shutil.rmtree(path, ignore_errors=True)

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.path, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()


def per_layer(manifest, name, family, shape, traffic, facts, tracer, peak):
    """The cell's per-layer metrics over the traced sub-window, and the
    numbers the result line's ``device`` and ``breakdown`` want: all of
    them from the capture cut to that sub-window."""
    from benchmark import readers, trace_reduce
    window_s = facts["traced"]["window_s"]
    capture = trace_reduce.load(tracer.path)
    trace = trace_reduce.sub_window(capture, window_s)
    print(f"run.py: device busy {trace_reduce.busy_seconds(capture):.6f} s in "
          f"the capture, {trace_reduce.busy_seconds(trace):.6f} s in the "
          f"{window_s:.6f} s sub-window"
          + ("" if trace is not capture else
             f" (NOT cut: no {trace_reduce.WINDOW_MARK} span)"), file=sys.stderr)
    ctx = {"facts": facts["traced"], "before": facts["traced"]["before"],
           "after": facts["traced"]["after"], "trace": trace, "peak": peak,
           "family": family, "shape": shape, "traffic": traffic}
    metrics = {}
    for m in manifest["per_layer"]:
        if name in m.get("workloads", [name]):
            value = readers.read(load("benchmark", "layer_metrics",
                                      m["name"] + ".json"), ctx)
            if value is not None:
                metrics[m["name"]] = (value, m["unit"])
    for pattern, bound in ctx.get("bounds", {}).items():
        print(f"run.py: roofline of /{pattern}/ is set by {bound}",
              file=sys.stderr)
    return metrics, {
        "busy_s": trace_reduce.busy_seconds(trace),
        "window_s": window_s}, {
        "device_ops": trace_reduce.top_ops(trace),
        "idle_gaps": trace_reduce.idle_gaps(trace)}


def compare(driver, family, config, cell, seed, shape, produced) -> dict:
    """{name: (value, what it is about)}: what the timed path produced
    against the plain reference of the cell's family."""
    from benchmark import correct, drivers
    import numpy as np
    if driver == "train":
        ring = drivers.train_batches(cell["traffic"], shape["vocab"], seed)
        ref = family.follow_training(
            shape, config["adam"], seed, ring[:len(produced["losses"])],
            cell["reference_rows"])
        return correct.training_numbers(produced, ref)
    w = drivers.seed_tree(family, shape, family.seed_key(seed),
                          drivers.master_dtype(config))
    worst, about, tokens = -1.0, "no request finished", 0
    for i, (seq, t0, n_new) in enumerate(produced):
        if len(seq) != t0 + n_new:
            return {"token_gap": (float("inf"),
                                  f"request {i}: {len(seq) - t0} of {n_new} tokens")}
        gaps = family.served_token_gaps(w, shape, seq, t0)
        tokens += len(gaps)
        if float(gaps.max()) > worst:
            worst, about = float(gaps.max()), \
                f"request {i} token {int(np.argmax(gaps)) + 1} of {tokens} compared"
    return {"token_gap": (worst if worst >= 0 else float("inf"), about)}


def _json_safe(x):
    """JSON has no infinity: a number that is not finite prints as 1e300."""
    if isinstance(x, dict):
        return {k: _json_safe(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_json_safe(v) for v in x]
    if isinstance(x, float) and not (x == x and abs(x) != float("inf")):
        return 1e300
    return x


def run_cell(name, manifest, cell, config, seed, seconds, trace, devices,
             peak, t_process=T_PROCESS) -> dict:
    """Everything after the look for a chip: set-up, the window, the
    comparison with the reference, the result line's object."""
    import jax
    from benchmark import correct, drivers
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(time.perf_counter())
        if event.endswith("backend_compile_duration") else None)
    family = drivers.family_of(config)
    if cell["driver"] == "train" and not hasattr(family, "follow_training"):
        raise SystemExit(f"run.py: {name} is a train cell, and its family "
                         f"{family.__name__} has no training reference "
                         f"(follow_training)")
    shape = family.shape_of(config)
    tracer = (Tracer(os.path.join(ROOT, ".bench_trace", name))
              if trace else None)
    setup = {}
    run = {"train": drivers.run_train, "serve_closed": drivers.run_serve}[cell["driver"]]
    facts, produced, free = run(
        family, config, cell, seed, seconds, tracer,
        lambda: setup.setdefault("s", time.perf_counter() - t_process))
    t_closed = time.perf_counter()
    in_window = sum(1 for t in compiles
                    if t_process + setup["s"] < t <= t_closed)
    print(f"run.py: memory_stats {devices[0].memory_stats()}", file=sys.stderr)
    if "counted" in facts:       # a trace-0 run's look at the layers
        print(f"run.py: counted in the window {json.dumps(facts['counted'])}",
              file=sys.stderr)
        print(f"run.py: ttft ms at 50/90/95/99/100% of {len(facts['ttft_s'])}: "
              + " ".join(f"{1e3 * percentile(facts['ttft_s'], q):.1f}"
                         for q in (50, 90, 95, 99, 100)), file=sys.stderr)
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)
    free()

    numbers = compare(cell["driver"], family, config, cell, seed, shape,
                      produced)
    ok, compared = correct.judge(numbers, cell["limits"])
    if cell["driver"] == "train":
        attempted, failed = facts["steps"], facts["failed"]
    else:
        attempted, failed = facts["requests_done"], facts["failed"]
        ok = ok and facts["tick_failures"] == 0
    ok = ok and failed == 0 and in_window == 0

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak_bytes)}
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed}
    if trace:
        metrics, dev, breakdown = per_layer(
            manifest, name, family, shape, cell["traffic"], facts, tracer,
            peak)
        device.update(dev)
        result["breakdown"] = breakdown
        shutil.rmtree(tracer.path, ignore_errors=True)
    else:
        metrics = end_to_end(manifest, name, facts, setup["s"])
    result["metrics"] = {k: {"value": v, "unit": u} for k, (v, u) in metrics.items()}
    result["device"] = device
    result["window_s"] = facts["window_s"]
    result["compiles_in_window"] = in_window
    result["check_s"] = time.perf_counter() - t_closed
    result["compared"] = compared
    return _json_safe(result)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest, cell, config = cell_files(args.workload)
    chips = next(w["chips"] for w in manifest["workloads"]
                 if w["name"] == args.workload)

    import jax
    # every program this process compiles goes to ONE fixed place: where
    # the machine says, or <checkout>/.jax_cache (the path is part of
    # the key).  The program sets none itself.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"run.py: {args.workload} needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} x {devices[0].platform!r} -- no CPU fallback",
              file=sys.stderr)
        return 1
    peaks = load("benchmark", "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        print(f"run.py: no peaks on record for device kind {kind!r} "
              f"(benchmark/peaks.json knows {sorted(peaks)})", file=sys.stderr)
        return 1

    result = run_cell(args.workload, manifest, cell, config, args.seed,
                      args.seconds, args.trace, devices[:chips], peaks[kind])
    for name, c in result["compared"].items():
        print(f"run.py: compared {name} = {c['value']:.6g} (limit {c['limit']:g}; "
              f"{c['about']})", file=sys.stderr)
    print(f"run.py: correct = {result['correct']}; failed {result['failed']} of "
          f"{result['attempted']}; compiles in the window "
          f"{result['compiles_in_window']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
