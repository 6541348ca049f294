"""Measured per-shape GEMM routing for quantized-weight matmuls.

PR 6 made int8/fp8 weights a STORAGE format: `QuantizedTensor` kernels
dequantize lazily to the compute dtype, so quantization buys HBM bytes but
zero FLOPs (round-5 on-chip MFU: 0.535 of bf16 peak — ROADMAP item 5).
This table makes low precision an EXECUTION path: ops/linear.py routes
every QuantizedTensor matmul through one of

* ``"dequant"`` — dequantize + dense matmul (PR-6 semantics; the fallback,
  and the only path on backends where low-precision dots lose);
* ``"dot"``     — dynamic per-token activation quantization + a real
  int8/fp8 ``dot_general`` with ``preferred_element_type`` accumulation
  (int32 / fp32), per-channel-tile scales applied after the accumulate;
* ``"pallas"``  — the in-repo tiled Pallas kernel (ops/quant_matmul.py)
  with the weight-scale application fused into the last K step.

Exactly like ops/sdpa_routing.py, the choice is a *checked-in table keyed
by shape*, regenerated from real chip measurements:

    scripts/chip_campaign.py --phases gemm  ->  chiprun_out/<log>  (gemm lines)
    scripts/update_gemm_table.py --log <log>
                                            ->  rewrites MEASURED_ROUTES

Resolution order in ``resolve`` (strongest wins):
  1. operator env overrides — DISTRIFUSER_TPU_GEMM (0/dequant/dot/pallas)
     and _BM/_BN/_BK tile pins — the research escape hatch, mirroring
     DISTRIFUSER_TPU_FLASH;
  2. a FORCED leaf policy ("dot"/"pallas" from DistriConfig.quant_compute);
  3. MEASURED_ROUTES (this file) — nearest measured M bucket, consulted
     only when MEASURED_BACKEND matches the running platform (a CPU
     structural campaign must never govern TPU routing, and vice versa);
  4. the analytic default: low-precision dot on TPU for non-trivial token
     counts (the MXU runs int8 at 2x the bf16 MAC rate), dequant on CPU
     (XLA's CPU int8 dot upcasts to int32 — all overhead, no win).

Routes are keyed (mode, round(log2 M)) where M is the flattened token
count of the activation — the axis along which the dequant-vs-quant-dot
ordering actually flips (at tiny M the per-token quantize + scale apply
overhead dominates; at large M the MAC-rate win does).  K/N ride along in
the comments for review.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass
from typing import Optional

import jax


@dataclass(frozen=True)
class GemmRoute:
    impl: str  # "dequant" | "dot" | "pallas"
    block_m: Optional[int] = None  # tuned Pallas tiles (None = defaults)
    block_n: Optional[int] = None
    block_k: Optional[int] = None


GEMM_IMPLS = ("dequant", "dot", "pallas")

# --- BEGIN MEASURED_GEMM_ROUTES (generated by scripts/update_gemm_table.py) ---
# provenance: cpu structural campaign r6 (backend-gated: governs CPU only; TPU re-bake pending), 2026-08-04
MEASURED_PROVENANCE = "cpu structural campaign r6 (backend-gated: governs CPU only; TPU re-bake pending), 2026-08-04"
MEASURED_BACKEND = "cpu"
MEASURED_ROUTES: dict = {
    ("fp8", 10): GemmRoute("dequant"),  # M=1024 K=512 N=2048: dequant=40.362ms vs dot=66.647, pallas=302.079
    ("fp8", 12): GemmRoute("dequant"),  # M=4096 K=512 N=512: dequant=36.909ms vs dot=73.812, pallas=211.106
    ("int8", 10): GemmRoute("dequant"),  # M=1024 K=512 N=2048: dequant=39.468ms vs dot=260.421, pallas=298.298
    ("int8", 12): GemmRoute("dequant"),  # M=4096 K=512 N=512: dequant=36.256ms vs dot=242.34, pallas=280.441
}
# --- END MEASURED_GEMM_ROUTES ---

# How far (in log2 steps of M) a measured entry generalizes; beyond this
# lookup() returns None and the analytic default decides (same contract as
# sdpa_routing.MAX_BUCKET_DISTANCE).
MAX_BUCKET_DISTANCE = 1.5

# Analytic default's minimum token count for the low-precision dot: below
# this the per-token activation quantize (3 elementwise passes over M*K)
# and the M*N scale apply are not paid back by halving M*K*N MAC time.
# The time/conditioning-embedding linears (M = batch, single digits) stay
# on dequant; every token-stream matmul (M = B*L, thousands) routes low.
DOT_MIN_M = 32


def lookup(mode: str, m: int) -> Optional[GemmRoute]:
    """Nearest measured route for (payload mode, token count M); None when
    the table has no in-range measurement (callers then use the analytic
    default).  Only consulted when MEASURED_BACKEND matches the running
    platform — measurements govern the platform they were taken on."""
    bucket = math.log2(max(m, 1))
    candidates = [(abs(k[1] - bucket), k)
                  for k in MEASURED_ROUTES if k[0] == mode]
    if not candidates:
        return None
    dist, best = min(candidates)
    if dist > MAX_BUCKET_DISTANCE:
        return None
    return MEASURED_ROUTES[best]


def resolve(mode: str, m: int, k: int, n: int, policy: str = "auto",
            platform: Optional[str] = None) -> GemmRoute:
    """Pick the GEMM execution path for one quantized matmul.

    ``mode`` is the payload mode ("int8"/"fp8"), ``m`` the flattened token
    count, ``k``/``n`` the reduction/output dims, ``policy`` the leaf's
    compute policy (QuantizedTensor.compute: "dequant" pins PR-6
    semantics; "dot"/"pallas" force; "auto" resolves here).  ``platform``
    overrides jax's device platform (tests).

    NOTE: like the SDPA env overrides, DISTRIFUSER_TPU_GEMM* are read at
    TRACE time — jit caches do not key on os.environ; rebuild the
    pipeline (or jax.clear_caches()) after changing them.
    """
    env = os.environ.get("DISTRIFUSER_TPU_GEMM")
    tiles = tuple(
        int(v) if (v := os.environ.get(f"DISTRIFUSER_TPU_GEMM_{ax}")) else None
        for ax in ("BM", "BN", "BK")
    )
    if env:
        impl = "dequant" if env == "0" else env
        if impl not in GEMM_IMPLS:
            raise ValueError(
                f"DISTRIFUSER_TPU_GEMM must be one of {GEMM_IMPLS} (or '0' "
                f"for dequant), got {env!r}"
            )
        return GemmRoute(impl, *tiles) if impl == "pallas" else GemmRoute(impl)
    if policy == "dequant":
        return GemmRoute("dequant")
    if policy in ("dot", "pallas"):
        return (GemmRoute(policy, *tiles) if policy == "pallas"
                else GemmRoute(policy))
    if policy != "auto":
        raise ValueError(
            f"unknown quantized-compute policy {policy!r} (expected "
            "'dequant', 'auto', 'dot', or 'pallas')"
        )
    platform = platform or jax.devices()[0].platform
    if MEASURED_BACKEND == platform:
        measured = lookup(mode, m)
        if measured is not None:
            return measured
    if platform == "cpu":
        return GemmRoute("dequant")
    return GemmRoute("dot" if m >= DOT_MIN_M else "dequant")
