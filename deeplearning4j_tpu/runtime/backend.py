"""Backend discovery and policy.

Replaces the ``Nd4jBackend`` ServiceLoader SPI (reference:
``nd4j-api org.nd4j.linalg.factory.Nd4jBackend``; CPU/CUDA backends in
``nd4j/nd4j-backends/nd4j-backend-impls/{nd4j-native,nd4j-cuda}``).  On TPU
the backend seam is PJRT: jax discovers platforms (tpu/cpu) and every op in
this framework lowers through XLA, so "selecting a backend" reduces to
choosing a platform, a default compute dtype, and donation policy.
"""
from __future__ import annotations

import dataclasses
import os
from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Backend:
    """Resolved execution environment.

    Mirrors what ``Nd4jBackend`` + ``Nd4jEnvironment`` expose to user code:
    platform identity, device inventory, default dtypes.
    """

    platform: str
    n_devices: int
    # Params are kept in `param_dtype`; matmul/conv compute runs in
    # `compute_dtype` (bf16 feeds the MXU at full rate on TPU).
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32

    @property
    def devices(self):
        return jax.devices()

    @property
    def is_tpu(self) -> bool:
        return self.platform == "tpu"

    def local_device_count(self) -> int:
        return jax.local_device_count()


@lru_cache(maxsize=None)
def backend() -> Backend:
    """Discover the active backend once per process.

    ``DL4J_TPU_COMPUTE_DTYPE=bfloat16`` switches matmul/conv compute to
    bf16 (the TPU-native default for training at speed); params stay f32.
    Analogue of ND4J's ``ND4J_*`` env-var runtime knobs
    (``org.nd4j.linalg.factory.Nd4jEnvironment``).
    """
    devs = jax.devices()
    platform = devs[0].platform
    compute = os.environ.get("DL4J_TPU_COMPUTE_DTYPE", "")
    compute_dtype = jnp.bfloat16 if compute in ("bfloat16", "bf16") else jnp.float32
    return Backend(platform=platform, n_devices=len(devs), compute_dtype=compute_dtype)


#: Peak dense bf16 FLOP/s of ONE chip, keyed by ``device_kind`` as JAX
#: reports it — the one MFU denominator ``bench.py`` and
#: ``TelemetryListener`` read.  Source: Google Cloud documentation,
#: "TPU v5e" system architecture page (197 TFLOP/s bf16 per chip).  A
#: device that is not here is an error, not a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_flops(device_kind: Optional[str] = None) -> float:
    """Peak bf16 FLOP/s of one chip of ``device_kind`` (default: the
    first attached device's).  Raises on a kind the table does not
    hold — an MFU against the wrong peak is worse than none; callers
    that want one off the chip pass their own denominator."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device kind {device_kind!r} "
            f"(known: {sorted(PEAK_BF16_FLOPS)}); pass the peak "
            "explicitly or add the kind, with its source, to "
            "runtime.backend.PEAK_BF16_FLOPS") from None


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns the directory
    it uses.  Entry points that run on the chip (``chip_smoke.py``,
    ``bench.py``, ``scripts/bench_*.py``, the examples) call this first,
    before anything compiles, so the processes of one chip call share
    compiled programs.

    Entry points that serve from multi-chip replicas do NOT call this
    and switch the cache off instead (``jax_enable_compilation_cache``):
    see ``GenerationServer``'s refusal.

    The directory is placed from OUTSIDE: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and no
    path is set in code.  Otherwise it is the fixed
    ``<checkout>/.jax_cache`` — the path is part of the cache key, so
    a directory that moves (tempfile, pid, time) never hits."""
    # programs that compile in under a second (a decode scan, an admit
    # bucket) are most of what a serving process compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
