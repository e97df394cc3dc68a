"""Data-parallel scaling-efficiency harness.

The measurement the reference never shipped in-tree (SURVEY §6 north star:
">=70% linear scaling" for ``SharedTrainingMaster`` DP): train the same
model at several mesh widths with a FIXED per-device batch (weak scaling,
the DP regime), report images/sec and efficiency vs linear.

Runs identically on the virtual CPU mesh (tests), one real chip, or a
pod — the mesh is the only variable.
"""
from __future__ import annotations

import json
import time
from typing import Callable, List, Optional, Sequence

import jax
import numpy as np

from deeplearning4j_tpu.parallel.mesh import MeshConfig
from deeplearning4j_tpu.parallel.trainer import ShardedTrainer


def measure_scaling(model_fn: Callable[[], object],
                    make_batch: Callable[[int], tuple],
                    per_device_batch: int = 32,
                    device_counts: Optional[Sequence[int]] = None,
                    n_steps: int = 10, warmup: int = 2,
                    out_path: Optional[str] = None) -> List[dict]:
    """``model_fn()`` builds a fresh model; ``make_batch(global_n)``
    returns (features, labels) for a global batch of ``global_n``
    examples.  Per-device batch stays constant — weak scaling.

    Returns one row per device count:
    ``{"devices", "examples_per_sec", "efficiency_vs_linear"}`` and
    writes them as a JSON artifact when ``out_path`` is given."""
    all_devs = jax.devices()
    if device_counts is None:
        device_counts = [n for n in (1, 2, 4, 8, 16, 32, 64)
                         if n <= len(all_devs)]
    rows: List[dict] = []
    for n in device_counts:
        model = model_fn()
        trainer = ShardedTrainer(model, MeshConfig(data=n),
                                 devices=all_devs[:n])
        # Rotate input buffers and end with a scalar readback, so the
        # window covers device completion (see bench.py header).
        batches = [make_batch(n * per_device_batch) for _ in range(2)]
        loss = None
        for i in range(warmup):
            loss = trainer.fit_batch(*batches[i % 2])
        if loss is not None:        # warmup=0 is legal
            float(loss)
        t0 = time.perf_counter()
        for i in range(n_steps):
            loss = trainer.fit_batch(*batches[i % 2])
        float(loss)
        dt = time.perf_counter() - t0
        gb = int(batches[0][0].shape[0])
        rows.append({"devices": n, "global_batch": gb,
                     "examples_per_sec": round(gb * n_steps / dt, 2)})
    base = rows[0]["examples_per_sec"] / rows[0]["devices"]
    for r in rows:
        r["efficiency_vs_linear"] = round(
            r["examples_per_sec"] / (base * r["devices"]), 4)
    if out_path:
        with open(out_path, "w") as f:
            json.dump({"metric": "dp_weak_scaling", "rows": rows}, f,
                      indent=1)
    return rows
