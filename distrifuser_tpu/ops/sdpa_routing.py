"""Which kernel runs one `ops.attention.sdpa` call: the table and the rule.

The reference always runs fused SDPA — torch picks the cuDNN/Flash backend
internally (/root/reference/distrifuser/modules/pp/attn.py:153).  Here the
choice between plain XLA softmax, the in-repo sequence-minor Pallas kernel
and jax.experimental's flash kernel (ops/flash_attention.py) is made in this
module and nowhere else: `route()` holds every gate, `TABLE` every measured
shape, and `sdpa` only dispatches on what `route()` returns.

A row of `TABLE` is earned by an A/B in a cell of the benchmark
(`benchmark/run.py`, route patched and nothing else) and names the ledger's
line as its origin (docs/PERF.md, "Self-attention routing").
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import NamedTuple, Optional


@dataclass(frozen=True)
class Route:
    impl: str  # "xla" | "inrepo" | "upstream" | "padded"
    block_q: Optional[int] = None  # tiles for the named flash kernel; sdpa
    block_k: Optional[int] = None  # fits them to the call (None = its own)
    kernel: Optional[str] = None  # "padded" only: the kernel it pads for


class Row(NamedTuple):
    kv_lo: int  # inclusive range of kv_len (aligned lengths: steps of 128)
    kv_hi: int
    route: Route
    origin: str  # where the row's verdict was measured


# The three rows "ledger, PR 25" are the shapes of the benchmark's cells: each
# on its own model-level A/B against upstream 256x1024 — the full-depth
# denoiser forward at published widths, batch 2, 1024^2 — and then the cells
# themselves (root PERF.md section 6, "PR 25"; SDXL UNet forward 126.85 ->
# 119.03 ms with both d=64 rows, PixArt-XL DiT forward 115.41 -> 92.54 ms).
# Rows key on kv_len, so the patch path (local Q, gathered KV) inherits them.
# The others are what a chained micro-benchmark of 2026-07-31 said on an
# earlier runtime.  Its `xla` verdicts were reversed at the model level on
# both sides of each island (7.03 s flash-routed against 8.33 s XLA-pinned
# at 1024^2 SDXL): they stand until a cell at 768^2 or 1536^2 decides them
# (ROADMAP D4).
_CELLS = "ledger, PR 25"
_MICRO = "2026-07 micro-benchmark, earlier runtime, not re-measured"
TABLE: dict = {
    # head dim: rows ascending in kv_len
    64: (
        Row(768, 1408, Route("inrepo", 1024, 1024), _CELLS),
        Row(1536, 2816, Route("xla"), _MICRO),
        Row(2944, 5760, Route("inrepo", 1024, 512), _CELLS),
        Row(5888, 8192, Route("xla"), _MICRO),
        Row(8320, 32768, Route("upstream", 512, 1024), _MICRO),
        Row(32896, 185344, Route("upstream", 256, 256), _MICRO),
    ),
    72: (
        Row(1536, 2816, Route("xla"), _MICRO),
        Row(2944, 5760, Route("inrepo", 1024, 512), _CELLS),
        Row(5888, 11520, Route("xla"), _MICRO),
    ),
}

# A head dim or a length TABLE does not list: XLA under this many keys, the
# upstream kernel with its own per-generation tiles from there.  The padded
# route starts at the same length.
FLASH_MIN_LEN = 1024
# head dims the padded route was swept over; beyond them unaligned stays XLA
_PADDED_MAX_HEAD_DIM = 256


def route(lq: int, lk: int, channels: int, heads: int, platform: str) -> Route:
    """The kernel for one sdpa call of [B, lq, channels] against lk keys.

    In order: `DISTRIFUSER_TPU_FLASH=0` pins XLA everywhere; a shape the
    kernels cannot tile (a length that is no multiple of 128, a head dim
    that is no multiple of 8) runs XLA, or — on the chip, from FLASH_MIN_LEN
    keys, head dim up to 256 — the upstream kernel padded and masked;
    `DISTRIFUSER_TPU_FLASH=1` forces a flash kernel at every aligned shape
    (in-repo in interpret mode on the CPU, which the kernel's tests use;
    upstream on the chip); the CPU runs XLA; then TABLE, then the default.

    The variable is read at TRACE time: jit caches do not key on
    os.environ, so a program traced before it changed keeps its route
    (`jax.clear_caches()`, or a fresh pipeline).
    """
    env = os.environ.get("DISTRIFUSER_TPU_FLASH")
    cpu = platform == "cpu"
    d = channels // heads
    tileable = channels % heads == 0 and d % 8 == 0
    if env == "0":
        return Route("xla")
    if not (tileable and lq % 128 == 0 and lk % 128 == 0):
        # unaligned-but-long (SD3's 4096+154 joint stream): padded flash cut
        # SD3-medium 20.2 -> 8.3 s against the chunked XLA softmax (one v5e,
        # 2026-07-31, upstream 8.32 s vs in-repo 13.54 s — the in-repo kernel
        # of that date; the sequence-minor one has not been A/B'd here)
        if (not cpu and tileable and lk >= FLASH_MIN_LEN
                and d <= _PADDED_MAX_HEAD_DIM):
            return Route("padded", kernel="upstream")
        return Route("xla")
    if env == "1":
        return Route("inrepo" if cpu else "upstream")
    if cpu:
        return Route("xla")
    for row in TABLE.get(d, ()):
        if row.kv_lo <= lk <= row.kv_hi:
            return row.route
    return Route("upstream" if lk >= FLASH_MIN_LEN else "xla")
