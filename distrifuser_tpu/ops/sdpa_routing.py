"""Measured per-shape SDPA routing table.

The reference always runs fused SDPA — torch picks the cuDNN/Flash backend
internally (/root/reference/distrifuser/modules/pp/attn.py:153).  Here the
backend choice (plain XLA softmax vs the in-repo sequence-minor Pallas kernel
vs jax.experimental's tuned flash kernel) is a *checked-in table keyed by shape*,
regenerated from real-chip measurements:

    scripts/chip_campaign.py   ->  chiprun_out/<log>   (attn/tune lines)
    scripts/update_sdpa_table.py --log chiprun_out/<log>
                               ->  rewrites MEASURED_ROUTES below

Resolution order in ops.attention.sdpa (strongest wins):
  1. operator env overrides — DISTRIFUSER_TPU_FLASH / _IMPL / _BQ / _BK —
     kept as the research escape hatch;
  2. MEASURED_ROUTES (this file) — nearest measured shape, TPU only;
  3. the analytic default (flash for long block-aligned sequences on TPU).

A Route names the winning impl and its tuned tiles (both flash kernels are
tile-sweepable; the campaign's tune/tune_upstream phases feed them).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class Route:
    impl: str  # "xla" | "inrepo" | "upstream"
    block_q: Optional[int] = None  # tuned tiles for the named flash kernel
    block_k: Optional[int] = None  # (None = that kernel's default)


# --- BEGIN MEASURED_ROUTES (generated by scripts/update_sdpa_table.py) ---
# provenance: one v5e, chained timing + 57600 probe, 2026-07-31
MEASURED_PROVENANCE = "one v5e, chained timing + 57600 probe, 2026-07-31"
MEASURED_ROUTES: dict = {
    (64, 10): Route("xla"),  # L=1024 h=20: xla=3.789ms vs inrepo=4.724, upstream=4.486
    (64, 12): Route("xla"),  # L=4096 h=24: xla=12.2ms vs inrepo=29.418, upstream=26.285
    (64, 14): Route("upstream", 512, 1024),  # L=16384 h=10: upstream=26.815ms vs inrepo=181.115
    (64, 16): Route("upstream", 256, 256),  # L=57600 h=10: upstream=655.1ms vs inrepo=1222.3
    (72, 12): Route("xla"),  # L=4096 h=16: xla=9.235ms vs inrepo=20.694, upstream=18.851
}
# --- END MEASURED_ROUTES ---

# Hand-maintained, NEVER rewritten by update_sdpa_table.py: routes proven at
# the FULL-MODEL level, which outranks the micro-benchmark.  Campaign r5's
# chained micro-harness has XLA ~10% ahead of tuned flash at L=4096, yet the
# complete 1024^2 SDXL program runs 7.03 s flash-routed vs 8.33 s XLA-pinned
# (campaign phases b1024 vs b1024_xla, forced-transfer timing) — in model context
# the unfused softmax pays fusion-boundary and HBM-traffic costs the isolated
# kernel chain never sees.  lookup() consults this dict FIRST, so a re-bake
# from micro lines cannot silently regress the model-level choice.
#
# PR 25, on chip (one v5e, 2026-09-28): the three shapes of the benchmark's
# cells moved from Route("upstream", 256, 1024) to the in-repo kernel, which
# is sequence-minor since that PR (ops/flash_attention.py): each entry on its
# own model-level A/B — the full-depth denoiser forward at the cell's
# published widths, batch 2, 1024^2, route patched and nothing else — and
# then the cells themselves (root PERF.md section 6, "PR 25").  Two things
# are gained per call: the four layout copies around the upstream kernel
# (q, k, v in, o out; tokens-on-lanes -> head-dim-on-lanes and back) become
# bitcasts, and the kernel's unrolled KV loop overlaps softmax with matmul
# (kernel alone, chained: 2.117 -> 1.596 ms at d=72 L=4096 h=16; 1.301 ->
# 1.008 ms at d=64 L=4096 h=10; 0.2116 -> 0.180 ms at d=64 L=1024 h=20).
# The entries key on kv_len, so the patch path (local Q, gathered KV)
# inherits them; no cell has timed it there yet.
MODEL_VALIDATED_OVERRIDES: dict = {
    # (head_dim, log2 bucket): Route
    (64, 12): Route("inrepo", 1024, 512),   # SDXL UNet forward 126.85 ->
                                            # 123.53 ms with this entry
                                            # alone, 119.03 with (64,10)
                                            # too; upstream 256x1024 was
                                            # b1024 7.034 s vs b1024_xla
                                            # 8.330 s, 2026-07-31
    (64, 10): Route("inrepo", 1024, 1024),  # SDXL UNet forward 126.85 ->
                                            # 122.40 ms alone; 60 calls a
                                            # step.  upstream 256x1024 was
                                            # b1024 6.220 s vs 7.034 s
                                            # xla-at-level-2, 2026-07-31
    (72, 12): Route("inrepo", 1024, 512),   # PixArt-XL DiT forward 115.41
                                            # -> 92.54 ms (512x512 93.23,
                                            # 1024x1024 92.72, 2048x512
                                            # 95.69).  upstream 256x1024
                                            # was 2.43 s vs 4.91 s xla an
                                            # image, though the chained
                                            # micro had xla 2x AHEAD
                                            # (9.2 vs 18.8 ms), 2026-07-31
}

# How far (in log2 steps of kv_len) a measured entry is allowed to
# generalize; beyond this lookup() returns None and the analytic default
# decides.  1.5 lets a measurement cover its neighbors (e.g. L=4096 covers
# 2896..5792-ish) without a long-L entry reaching down to short sequences.
MAX_BUCKET_DISTANCE = 1.5

# Overrides are validated at ONE model configuration, so they generalize
# far less than micro measurements: 0.5 keeps each override inside its own
# bucket's neighborhood (the (64,10) entry governs L~724-1448, not the
# sub-1024 shapes whose only data says XLA).
OVERRIDE_MAX_BUCKET_DISTANCE = 0.5


def lookup(lk: int, head_dim: int) -> Optional[Route]:
    """Nearest measured route for (kv_len, head_dim); None when the table has
    no measurement for this head_dim (callers then use the analytic default).

    KV length generalizes on a log2 bucket: the latency ordering of the
    implementations changes with sequence-length scale, not with exact L.
    A measurement more than MAX_BUCKET_DISTANCE log2 steps away does NOT
    govern (a lone L=16384 entry must not override the analytic
    short-sequence gate at L=1024) — out-of-range lengths fall through to
    the analytic default.
    """
    # Overrides and measured entries compete under the SAME nearest-bucket
    # contract; an override only shadows the measured table when its bucket
    # is at least as close to the query as every measured candidate (it is
    # model-validated AT its bucket, not at buckets a closer measurement
    # covers — e.g. the (64,12) override must not capture L=1536 from a
    # 0.58-bucket-away (64,10) XLA measurement).
    bucket = math.log2(max(lk, 1))
    candidates = [(abs(k[1] - bucket), 0, k)  # 0 sorts overrides first on tie
                  for k in MODEL_VALIDATED_OVERRIDES if k[0] == head_dim
                  and abs(k[1] - bucket) <= OVERRIDE_MAX_BUCKET_DISTANCE]
    candidates += [(abs(k[1] - bucket), 1, k)
                   for k in MEASURED_ROUTES if k[0] == head_dim]
    if not candidates:
        return None
    dist, source, best = min(candidates)
    if dist > MAX_BUCKET_DISTANCE:
        return None
    return (MODEL_VALIDATED_OVERRIDES if source == 0
            else MEASURED_ROUTES)[best]
