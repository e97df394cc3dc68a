#!/usr/bin/env python
"""Speculative-decode benchmarks -> SERVING_SPEC_r11.json +
SERVING_SPEC_r20.json.

r11 (greedy): draft-model K-ahead generation with single-dispatch
batched verification through the paged ``GenerationServer`` —
accepted-tokens/s at K in {2, 4} vs the non-speculative
``tick_batch``-fused baseline on identical geometry, with the draft
acceptance rate per rung and in-window byte parity against the
baseline outputs.

r20 (sampled, ISSUE 20): rejection-resampling speculation over a
mixed greedy+sampled two-tenant trace at temperature in {0.4, 0.8} x
{fixed K in {2, 4}, acceptance-adaptive K} vs the non-speculative
sampled baseline — greedy rows byte-checked in-window, every compile
variant (including each adaptive draft depth) warmed off-window.

Acceptance bars: r11 needs accepted-tokens/s exceeding the
non-speculative baseline on a self-draft rung; r20 needs sampled
tokens/s >= 1.3x the non-spec sampled baseline at temperature 0.8
(smoke config) and the adaptive rung matching or beating every fixed
K on the same trace.

``--smoke`` runs the tiny CPU configs (the artifact CI records —
JAX_PLATFORMS=cpu friendly); the default geometry needs the real chip.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    from deeplearning4j_tpu.runtime.backend import enable_compile_cache
    enable_compile_cache()
    smoke = "--smoke" in sys.argv[1:]
    if not smoke:
        import jax
        assert jax.default_backend() == "tpu", \
            "needs the real chip (or pass --smoke for the CPU config)"
    from bench import bench_spec_sampled, bench_speculative

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    r11 = bench_speculative(smoke=smoke)
    print(json.dumps(r11))
    with open(os.path.join(root, "SERVING_SPEC_r11.json"), "w") as f:
        json.dump(r11, f, indent=1)
    print("wrote SERVING_SPEC_r11.json")
    ok11 = r11["vs_baseline"] > 1.0 and any(
        r["acceptance_rate"] == 1.0 for r in r11["ladder"]
        if r["draft"] == "self_full")

    r20 = bench_spec_sampled(smoke=smoke)
    print(json.dumps(r20))
    with open(os.path.join(root, "SERVING_SPEC_r20.json"), "w") as f:
        json.dump(r20, f, indent=1)
    print("wrote SERVING_SPEC_r20.json")
    hot = max(float(t) for t in r20["nonspec_tokens_per_sec"])
    hot_rungs = [r for r in r20["ladder"] if r["temperature"] == hot]
    ok20 = (max(r["vs_nonspec"] for r in hot_rungs) >= 1.3
            and r20["adaptive_matches_fixed"])

    print("acceptance r11:", "OK" if ok11 else "FAIL")
    print("acceptance r20:", "OK" if ok20 else "FAIL")
    return 0 if (ok11 and ok20) else 1


if __name__ == "__main__":
    sys.exit(main())
