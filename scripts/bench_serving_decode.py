#!/usr/bin/env python
"""Paged-KV shared-prefix serve benchmark -> SERVING_DECODE_r07.json:
1/4/16 streams sharing one long system prompt through the paged
``GenerationServer`` — TTFT p50/p99 per rung, the cold-prefill vs
prefix-hit TTFT ratio (a hit prefills only the uncached suffix), and
concurrent-streams-at-fixed-HBM for the stripe vs block layouts at
mixed request lengths (a short request pins ceil(len/block_size)
blocks instead of a whole [max_len] stripe, and the shared system
prompt is resident ONCE).

Acceptance bar (ISSUE 7): prefix-hit TTFT strictly below cold TTFT,
and >= 2x concurrent streams at the stripe pool's HBM footprint.

``--smoke`` runs the tiny CPU config (the artifact CI records —
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
    from bench import bench_serving_decode

    result = bench_serving_decode(smoke=smoke)
    print(json.dumps(result))
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "SERVING_DECODE_r07.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print("wrote", path)
    ok = (result["prefix_hit_ttft_ratio"] < 1.0
          and result["vs_baseline"] >= 2.0)
    print("acceptance:", "OK" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
