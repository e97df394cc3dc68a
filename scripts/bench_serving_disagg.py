#!/usr/bin/env python
"""Disaggregated prefill/decode + tiered KV benchmark ->
SERVING_DISAGG_r14.json (ISSUE 14): a mixed trace of long-prompt
admissions interleaved with short-decode streams through a unified
fleet vs a role-split (prefill + decode) fleet — short-stream TTFT
p50/p99 under both — plus the tiered prefix cache's tier-hit TTFT vs
cold re-prefill at a prefix footprint larger than the device pool.

Acceptance bar (ISSUE 14): disagg short-stream TTFT p99 <= the
unified fleet's under the same trace, and tier-hit TTFT < cold
re-prefill TTFT (tier_hit_ttft_ratio < 1).  The disagg probe output
is byte-checked against the unified fleet's in-window.

``--smoke`` runs the tiny CPU config (the artifact CI records —
JAX_PLATFORMS=cpu friendly); on the shared-host CPU the role split
relieves scheduler serialization, not chip contention — the TPU
geometry is where the replicas map to real chips.
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
    from bench import bench_serving_disagg

    result = bench_serving_disagg(smoke=smoke)
    print(json.dumps(result))
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "SERVING_DISAGG_r14.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print("wrote", path)
    ok = (result["vs_baseline"] is not None
          and result["vs_baseline"] >= 1.0
          and result["tier"]["tier_hit_ttft_ratio"] < 1.0)
    print("acceptance:", "OK" if ok else "FAIL",
          f"(disagg p99 {result['value']}s, unified/disagg "
          f"{result['vs_baseline']}x, tier-hit ratio "
          f"{result['tier']['tier_hit_ttft_ratio']})")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
