#!/usr/bin/env python
"""Imported-causal-graph fine-tune ON SILICON (round-4 review item 6's
'done' bar): import the toy frozen GPT (t=512, additive tril mask),
fuse to causal fused_attention, fine-tune with the flash kernel's
CAUSAL path route-probe-verified, record CAUSAL_IMPORT_r05.json."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    assert jax.default_backend() == "tpu", "probe needs the real chip"
    from deeplearning4j_tpu import kernels
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.autodiff.rewrites import optimize_for_tpu
    from deeplearning4j_tpu.autodiff.tf_import import import_frozen_pb
    from deeplearning4j_tpu.optimize.updaters import Adam

    pb = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tests", "fixtures",
        "gpt_toy_frozen.pb")
    sd = import_frozen_pb(pb)
    stats = optimize_for_tpu(sd, compute_dtype="bfloat16")
    fused = [n for n in sd.ops if n.op_name == "fused_attention"]
    causal_sites = sum(1 for n in fused if n.attrs.get("causal"))

    pooled = sd.reduce_mean(sd.vars["Identity"], axis=1)
    w = sd.var("cls_W", np.random.default_rng(0).normal(
        scale=0.02, size=(64, 2)).astype(np.float32))
    logits = sd.matmul(pooled, w, name="logits")
    labels = sd.placeholder("labels", (None,), "int32")
    per_ex = sd.op("sparse_softmax_cross_entropy_with_logits", labels,
                   logits)
    sd.set_loss_variables(sd.reduce_mean(per_ex, name="loss"))
    sd.set_training_config(TrainingConfig(
        updater=Adam(learning_rate=2e-5),
        data_set_feature_mapping=["i"],
        data_set_label_mapping=["labels"],
        compute_dtype="bfloat16"))

    batch, t = 32, 512
    rng = np.random.default_rng(0)
    step_fn, updater = sd._train_step_fn(["i", "labels"])
    params = {k: jnp.asarray(v) for k, v in sd._param_values().items()}
    opt_state = updater.init_state(params)
    bufs = []
    for _ in range(4):
        ids = rng.integers(0, 500, (batch, t)).astype(np.int32)
        # a learnable lexical rule: class = whether token 7 appears
        labs = (np.any(ids == 7, axis=1)).astype(np.int32)
        bufs.append({"i": jnp.asarray(ids), "labels": jnp.asarray(labs)})

    kernels.reset_route_log()
    params, opt_state, loss = step_fn(
        params, opt_state, jnp.asarray(0, jnp.int32), bufs[0])
    loss_first = float(loss)
    routes = kernels.route_log()
    flash_routes = sum(1 for r in routes if r[0] == "flash")
    n_steps = 60
    t0 = time.perf_counter()
    for i in range(n_steps):
        params, opt_state, loss = step_fn(
            params, opt_state, jnp.asarray(i + 1, jnp.int32),
            bufs[i % 4])
    loss_last = float(loss)
    dt = time.perf_counter() - t0
    out = {
        "metric": "imported_causal_gpt_finetune",
        "fused_attention_sites": stats["attention"],
        "causal_sites": causal_sites,
        "flash_routes_traced": flash_routes,
        "routes": [list(r) for r in routes[:8]],
        "batch": batch, "seq_len": t,
        "ms_per_step": round(dt / n_steps * 1e3, 3),
        "loss_first": round(loss_first, 4),
        "loss_last": round(loss_last, 4),
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "CAUSAL_IMPORT_r05.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
