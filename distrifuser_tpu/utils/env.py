"""Environment checks and device facts for the TPU runtime.

TPU-native analog of the reference's CUDA/NCCL environment gate
(/root/reference/distrifuser/utils.py:6-16, `check_env`): instead of asserting
CUDA >= 11.3 and torch >= 2.2 (NCCL-inside-CUDA-graph support), we assert the
JAX line the code is written for, and report which backend (tpu / cpu) the
mesh will be built on.  There is no CUDA-graph prerequisite on TPU: a single
`jax.jit`-compiled step already gives static-shape replay with collectives
fused into the program.

Also the one place that knows two facts about the machine the entry scripts
(`chip_smoke.py`, `bench.py`, `scripts/`) need before their first compile:
where the persistent compilation cache lives (`setup_compile_cache`) and what
the attached chip can do at best (`device_peaks`).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional

import jax

# The code calls `jax.shard_map(..., check_vma=...)`, `pltpu.CompilerParams`,
# `jax.extend.core` and `deserialize_and_load(..., execution_devices=...)`
# directly: the installed line (pyproject.toml pins the same floor).
_MIN_JAX = (0, 9, 0)


def _version_tuple(v: str) -> tuple[int, ...]:
    parts = []
    for piece in v.split(".")[:3]:
        digits = "".join(ch for ch in piece if ch.isdigit())
        parts.append(int(digits) if digits else 0)
    return tuple(parts)


def check_env() -> None:
    """Raise if the JAX runtime is older than the line the code is written for."""
    if _version_tuple(jax.__version__) < _MIN_JAX:
        raise RuntimeError(
            f"distrifuser_tpu requires jax >= {'.'.join(map(str, _MIN_JAX))}; "
            f"found {jax.__version__}"
        )


def default_backend() -> str:
    """The platform JAX will run on ('tpu', 'cpu', 'gpu').

    Callers key behavior (bf16 default dtype, kernel routing) on it.  A
    backend that fails to initialize RAISES here: answering "cpu" for a TPU
    that did not come up would silently turn the model float32 and every
    number after it into a CPU number.
    """
    return jax.default_backend()


def is_power_of_2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere stable; returns the
    directory in use.  Entry scripts call this before their first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
    machine's owner chose the place — nothing is set in code.  Otherwise the
    cache goes to ``<checkout>/.jax_cache`` (git-ignored): the path is part
    of the cache key, so it must not move between runs.  JAX's own
    minimum-compile-time threshold (1 s) is left alone: a raised floor left
    the smaller per-step and encoder programs uncached.  The library itself
    (`pipelines.py`, `serve/`) never calls this; see docs/SERVING.md.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    cache_dir = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


# ---------------------------------------------------------------------------
# device peaks
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DevicePeaks:
    bf16_tflops: float  # dense bf16 matmul peak, TFLOP/s per chip
    int8_tops: float    # dense int8 matmul peak, TOP/s per chip
    hbm_gbps: float     # HBM bandwidth, GB/s per chip


# Keyed by `jax.devices()[0].device_kind`.  Source: Google Cloud TPU
# documentation, "TPU v5e" system architecture page (per-chip: 197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s).
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(bf16_tflops=197.0, int8_tops=393.0,
                               hbm_gbps=819.0),
}


def device_peaks(device_kind: Optional[str] = None) -> DevicePeaks:
    """Published peaks of the attached chip (or of ``device_kind``).  A kind
    that is not in the table is an error, never a default: a utilization
    computed against another chip's peak is not a measurement."""
    kind = device_kind if device_kind is not None \
        else jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {kind!r}; add it to "
            f"DEVICE_PEAKS in {__name__} with its source "
            f"(known: {sorted(DEVICE_PEAKS)})"
        ) from None
