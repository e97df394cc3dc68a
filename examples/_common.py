"""Shared example bootstrap.

The reference's public face is the separate ``dl4j-examples`` repo;
these scripts are its TPU-native equivalent, one per BASELINE.json
config.  Every example takes ``--smoke``: tiny shapes on a virtual
8-device CPU mesh, exactly what CI runs (``tests/test_examples.py``).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def example_args(description: str) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=description)
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes, CPU virtual 8-device mesh (CI)")
    return p.parse_args()


def setup_platform(smoke: bool) -> None:
    """--smoke forces the CPU platform BEFORE jax initializes; a full
    run takes the default platform and shares compiled programs
    through the persistent compile cache."""
    if not smoke:
        from deeplearning4j_tpu.runtime.backend import (
            enable_compile_cache)
        enable_compile_cache()
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
