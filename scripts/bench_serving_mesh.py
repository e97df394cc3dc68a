#!/usr/bin/env python
"""Mesh-sharded decode benchmark -> SERVING_MESH_r17.json (ISSUE 17):
one replica spanning chips.  The same trace runs through a tp=1
(unsharded) and a tp=2 (data x tp NamedSharding mesh) replica —
new-tokens/s, TTFT p50/p99 and the speculative acceptance rate per
rung, outputs byte-compared across rungs so the bench fails rather
than report a rate that broke parity.

Acceptance bar (ISSUE 17): tp=2 new-tokens/s >= 0.7x the tp=1 rate —
the sharded tick's all-gather overhead never costs more than 30% of
the single-chip rate, even on the CPU smoke where both rungs share
the same silicon (on TPU the rung buys real HBM bandwidth and the
ladder climbs instead).

``--smoke`` runs the tiny CPU config (the artifact CI records); the
XLA host-device force below makes a 2-device slice available there.
The default geometry needs the real chips.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# a tp=2 rung needs two devices even on the CPU smoke; no-op when the
# flag is already set (or in-process under tests/conftest.py)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2").strip()


def main():
    # multi-chip replicas: a decode program loaded from the persistent
    # compile cache halts the chip on this installation (PERF.md, PR
    # 21), and GenerationServer refuses to start with the cache on
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    smoke = "--smoke" in sys.argv[1:]
    if not smoke:
        assert jax.default_backend() == "tpu", \
            "needs the real chips (or pass --smoke for the CPU config)"
    from bench import bench_serving_mesh

    result = bench_serving_mesh(smoke=smoke)
    print(json.dumps(result))
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "SERVING_MESH_r17.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print("wrote", path)
    ran = [r for r in result["ladder"] if "skipped" not in r]
    ok = (result["vs_baseline"] >= 0.7
          and len(ran) == len(result["ladder"])
          and all(r["spec_acceptance_rate"] == 1.0 for r in ran))
    print("acceptance:", "OK" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
