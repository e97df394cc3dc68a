"""Test environment: force an 8-device virtual CPU platform so
multi-device sharding tests run real XLA collectives without TPU
hardware — the analogue of DL4J's loopback-Aeron / Spark-local[N]
distributed tests (SURVEY.md §4).

The platform is set through jax.config before any backend initializes,
so the suite runs on the CPU whatever accelerator the machine holds (a
chip belongs to one process at a time; tests never take it).
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)
