#!/usr/bin/env python
"""ParallelInference dynamic-batching benchmark (round-3 review item
8): p50/p99 request latency + sustained throughput vs offered
concurrency on the real chip, written to chiprun_out/SERVING.json.

Model: zoo SimpleCNN at 48x48x3 (a realistic serving-sized CNN).  Each
client thread issues single-example blocking ``output(x)`` requests in
a closed loop; the server coalesces concurrent requests into one
bucketed forward (the DL4J BATCHED inference mode).  Latency is
per-request wall time; a 2 s warmup per concurrency level is discarded.
"""
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def run_level(pi, n_clients: int, seconds: float = 6.0,
              warmup: float = 2.0):
    rng = np.random.default_rng(0)
    xs = [rng.normal(size=(1, 48, 48, 3)).astype(np.float32)
          for _ in range(8)]
    stop = time.perf_counter() + warmup + seconds
    t_measure = time.perf_counter() + warmup
    lat, count = [], [0]
    lock = threading.Lock()

    def client(cid):
        i = 0
        while True:
            now = time.perf_counter()
            if now >= stop:
                return
            t0 = time.perf_counter()
            pi.output(xs[(cid + i) % len(xs)])
            t1 = time.perf_counter()
            i += 1
            if t0 >= t_measure and t1 < stop:
                # count only requests fully inside the window — else
                # up to n_clients stragglers overstate req/s
                with lock:
                    lat.append(t1 - t0)
                    count[0] += 1

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    lat = np.asarray(sorted(lat))
    return {
        "concurrency": n_clients,
        "requests": int(count[0]),
        "throughput_req_s": round(count[0] / seconds, 1),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
        "p90_ms": round(float(np.percentile(lat, 90)) * 1e3, 2),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
    }


def model_time_ms(model, batch: int):
    """Per-forward time at this batch size with per-call costs
    differenced out: the forward runs inside one jitted ``lax.scan``
    at two scan lengths, and the difference is divided by the extra
    iterations."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.normal(size=(4, batch, 48, 48, 3)), jnp.float32)
    params, state = model.params_tree, model.state_tree

    def fwd(x):
        return jnp.sum(model._forward_infer(params, state, x)
                       .astype(jnp.float32))

    def make_run(n):
        @jax.jit
        def run(xs, seed):
            xs = xs + seed
            def body(c, i):
                return c + fwd(xs[i % 4]), None
            c, _ = lax.scan(body, 0.0, jnp.arange(n))
            return c
        return run

    r1, r2 = make_run(8), make_run(72)
    _ = float(r1(xs, 1e-6)); _ = float(r2(xs, 2e-6))
    def wall(r, seed):
        t0 = time.perf_counter()
        _ = float(r(xs, seed))
        return time.perf_counter() - t0
    t1 = min(wall(r1, 3e-6), wall(r1, 4e-6), wall(r1, 5e-6))
    t2 = min(wall(r2, 6e-6), wall(r2, 7e-6), wall(r2, 8e-6))
    return (t2 - t1) / 64 * 1e3


def main():
    import jax
    from deeplearning4j_tpu.runtime.backend import enable_compile_cache
    enable_compile_cache()
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    from deeplearning4j_tpu.zoo.simple_cnn import SimpleCNN

    backend = jax.default_backend()
    model = SimpleCNN(n_classes=10, input_shape=(48, 48, 3)).init_graph()
    rows = []
    with ParallelInference(model, batch_limit=64, queue_limit=256,
                           timeout_ms=2.0) as pi:
        pi.output(np.zeros((1, 48, 48, 3), np.float32))  # compile
        for n in (1, 4, 16, 64):
            rows.append(run_level(pi, n))
            print(json.dumps(rows[-1]), flush=True)
    mt = {str(b): round(model_time_ms(model, b), 3)
          for b in (1, 16, 64)}
    out = {"backend": backend, "model": "SimpleCNN 48x48x3",
           "batch_limit": 64, "mode": "BATCHED (dynamic coalescing, "
           "power-of-two padding buckets)", "levels": rows,
           "device_model_time_ms_per_forward": mt,
           "model_time_note": "per batched forward, differential "
           "two-scan-length protocol (per-call costs cancel)"}
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out", "SERVING.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", path)


if __name__ == "__main__":
    main()
