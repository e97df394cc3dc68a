#!/usr/bin/env python
"""KV-cache incremental-decode benchmark on the real chip ->
chiprun_out/GENERATION.json: steady-state decode rate for `zoo.Gpt` greedy
decoding through `models.generation.TransformerGenerator` (batched
prompt prefill + one jitted decode lax.scan; the transformer
``rnnTimeStep`` serving path), measured against the params-bandwidth
IDEAL for this chip — the number a decode step cannot beat because
every step must stream the full parameter set from HBM.

Protocol: the whole generate() call is ONE device program; two call
sizes (n_new 128 vs 512) difference out the prefill+fixed costs for
the pure per-step rate; different prompts per call; best of 3.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

V5E_HBM_GBPS = 820.0          # v5e HBM bandwidth


def main():
    import jax
    from deeplearning4j_tpu.runtime.backend import enable_compile_cache
    enable_compile_cache()
    from deeplearning4j_tpu.models.generation import TransformerGenerator
    from deeplearning4j_tpu.zoo.gpt import Gpt

    assert jax.default_backend() == "tpu", "needs the real chip"
    b, t0 = 8, 512
    m = Gpt(seq_len=t0, max_len=t0 + 512)
    net = m.init_graph()
    gen = TransformerGenerator(net, compute_dtype="bfloat16")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, m.vocab_size, (b, t0)).astype(np.int32)
               for _ in range(8)]

    def timed(n_new, ps):
        _ = gen.generate(ps[0], n_new=n_new)          # compile
        best = 1e9
        for i in range(3):
            t_ = time.perf_counter()
            _ = gen.generate(ps[1 + i], n_new=n_new)
            best = min(best, time.perf_counter() - t_)
        return best

    t_short = timed(128, prompts[:4])
    t_long = timed(512, prompts[4:])
    per_step = (t_long - t_short) / (512 - 128)       # s per decode tick
    steps_per_sec = 1.0 / per_step
    new_tok_s = b * steps_per_sec                     # batched step

    # params-bandwidth ideal: every decode tick streams the params once
    n_params = sum(int(np.prod(np.shape(l)))
                   for l in jax.tree_util.tree_leaves(net.params_tree))
    bytes_per_step = 2.0 * n_params                   # bf16
    ideal_steps = V5E_HBM_GBPS * 1e9 / bytes_per_step
    result = {
        "metric": "gpt_kv_cache_decode",
        "model": "zoo.Gpt GPT-2-small-shaped (6x128 heads)",
        "batch": b, "prompt_len": t0,
        "prefill": "batched causal forward (r5; r4 consumed the "
                   "prompt one cached step at a time)",
        "seconds_per_call_128": round(t_short, 3),
        "seconds_per_call_512": round(t_long, 3),
        "decode_steps_per_sec": round(steps_per_sec, 1),
        "new_tokens_per_sec": round(new_tok_s, 1),
        "params": n_params,
        "params_bandwidth_ideal_steps_per_sec": round(ideal_steps, 1),
        "pct_of_bandwidth_ideal": round(
            100.0 * steps_per_sec / ideal_steps, 1),
        "note": "per-step rate from the (512-128)-tick call "
                "difference, so prefill and per-call costs "
                "cancel; the ideal line assumes one full bf16 "
                "parameter stream per tick (KV-cache reads add ~6% "
                "at these shapes and are not modeled)",
    }
    print(json.dumps(result))
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out", "GENERATION.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print("wrote", path)


if __name__ == "__main__":
    main()
