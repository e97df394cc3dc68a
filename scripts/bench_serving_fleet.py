#!/usr/bin/env python
"""Multi-tenant serving-fleet benchmark -> SERVING_FLEET_r09.json:
1/2/4 ``GenerationServer`` replicas behind the ``ServingFleet``
admission router under a mixed 2-tenant load — a hot tenant sharing
one long system prompt (prefix-affinity should route it to the warm
replica) and a cold tenant with unique prompts (least-loaded spread).
Per rung: aggregate new-tokens/s, per-tenant TTFT p50/p99, and the
affinity hit rate.

Acceptance bar (ISSUE 9): the repeated-system-prompt tenant rides the
warm replica's prefix cache — affinity_hit_rate > 0 at every rung
with more than one replica (and at the 1-replica rung, where every
same-prefix dispatch is trivially affinity once seeded).

``--smoke`` runs the tiny CPU config (the artifact CI records —
JAX_PLATFORMS=cpu friendly); the default geometry needs the real
chip, where replicas map to chips and the ladder measures scaling
rather than router overhead.
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
    from bench import bench_serving_fleet

    result = bench_serving_fleet(smoke=smoke)
    print(json.dumps(result))
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "SERVING_FLEET_r09.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print("wrote", path)
    ok = all(r["affinity_hit_rate"] > 0 for r in result["ladder"])
    print("acceptance:", "OK" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
