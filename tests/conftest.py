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


def transfer_counts() -> dict:
    """{series: value} of the serving scheduler's transfer and dispatch
    counters, with the two they are held against."""
    from deeplearning4j_tpu import telemetry
    counters = telemetry.get_registry().snapshot()["counters"]
    return {k: v for k, v in counters.items() if k.split("{")[0] in (
        "generation_server_host_transfers_total",
        "generation_server_dispatches_total",
        "generation_server_host_syncs_total",
        "generation_server_admitted_total")}


def one_transfer_each_way(before: dict) -> dict:
    """Holds ISSUE 32's rule over the dispatches since the
    ``transfer_counts()`` given: one device-to-host read a scan, one
    host-to-device array an admission, and no transfer besides (but a
    speculative round's per-slot depths, one array in).  Returns the
    deltas by short name."""
    d = {k.replace("generation_server_", ""): v - before.get(k, 0)
         for k, v in transfer_counts().items()}
    moved = lambda site, way: d[
        f'host_transfers_total{{site="{site}",dir="{way}"}}']
    ran = lambda program: d[f'dispatches_total{{program="{program}"}}']
    assert moved("scan", "d2h") == ran("scan") == d["host_syncs_total"] > 0
    assert moved("admit", "h2d") == ran("admit") == d["admitted_total"] > 0
    assert moved("admit", "d2h") == 0
    assert moved("scan", "h2d") in (0, ran("scan"))
    assert moved("kill", "h2d") == ran("kill") and moved("kill", "d2h") == 0
    return d


@pytest.fixture
def guarded_scheduler(monkeypatch):
    """Every ``GenerationServer`` scheduler thread started in the test
    runs under ``jax.transfer_guard("disallow")``: an IMPLICIT transfer
    on it (a numpy operand or scalar handed to a program, a key built on
    the host) raises, which fails the requests in flight; the explicit
    ones are the server's two counted helpers.  (The CPU backend's
    guard sees host-to-device moves only: device-to-host reads are held
    by the counters.)  Gives ``counts()`` and ``held(before)``."""
    import types

    from deeplearning4j_tpu.parallel import GenerationServer
    run = GenerationServer._run

    def guarded(self, epoch):
        with jax.transfer_guard("disallow"):
            return run(self, epoch)

    monkeypatch.setattr(GenerationServer, "_run", guarded)
    return types.SimpleNamespace(counts=transfer_counts,
                                 held=one_transfer_each_way)
