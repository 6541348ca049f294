"""Pallas tiled quantized matmul: int8/fp8 MACs on the MXU, scales fused.

The ``"pallas"`` rung of ops/gemm_routing.py.  The XLA ``"dot"`` route
already gets the 2x MXU int8 rate; this kernel exists for the shapes where
XLA's epilogue placement loses — the per-channel-tile weight-scale
application is fused into the kernel's last K step, so the int32
accumulator never round-trips through HBM before scaling (the classic
quantized-GEMM epilogue fusion), and tile sizes are sweepable by the chip
campaign exactly like the flash-attention kernels.

Contract (what ops/linear.py feeds it):

* ``xq``  [M, K]  — the activation, already dynamically quantized per
  token to the weight's payload dtype (int8 / float8_e4m3fn);
* ``wq``  [K, N]  — the QuantizedTensor payload;
* ``sw``  [N] fp32 — per-OUTPUT-CHANNEL weight scales, channel_tile
  already expanded (QuantizedTensor.channel_scale);
* returns [M, N] fp32 = (xq @ wq) * sw — the caller applies the
  per-token activation scale and casts (both fuse into surrounding
  elementwise work under XLA).

Accumulation is int32 for int8 payloads and fp32 for fp8
(``preferred_element_type``), the same discipline as the XLA dot route.
Inputs pad to tile multiples with zeros (zero MACs are exact); padded
output rows/columns are sliced off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Default tiles: MXU-friendly (int8 min tile is (32, 128); 512 deep K
# amortizes the accumulator read-modify-write).  The chip campaign's gemm
# phase sweeps these; measured winners land in gemm_routing.MEASURED_ROUTES.
DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_K = 512


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _qmm_kernel(x_ref, w_ref, sw_ref, o_ref, acc_scr):
    k_idx = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k_idx == 0)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    acc_scr[:] += jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=acc_scr.dtype,
    )

    @pl.when(k_idx == nk - 1)
    def _():
        # fused epilogue: per-channel-tile weight scale applied while the
        # accumulator is still in VMEM
        o_ref[:] = acc_scr[:].astype(jnp.float32) * sw_ref[:]


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def quant_matmul(xq, wq, sw, *, block_m: int = None, block_n: int = None,
                 block_k: int = None, interpret: bool = False):
    """(xq @ wq) * sw with low-precision MACs (module docstring)."""
    if xq.ndim != 2 or wq.ndim != 2:
        raise ValueError(
            f"quant_matmul takes 2D operands, got {xq.shape} @ {wq.shape}"
        )
    m, k = xq.shape
    k2, n = wq.shape
    if k != k2 or sw.shape != (n,):
        raise ValueError(
            f"shape mismatch: x [M={m}, K={k}], w [K={k2}, N={n}], "
            f"sw {sw.shape} (want [N])"
        )
    acc_dtype = jnp.int32 if wq.dtype == jnp.int8 else jnp.float32

    # clamp tiles to the (tile-aligned) problem, then pad to multiples
    bm = min(block_m or DEFAULT_BLOCK_M, _round_up(m, 32))
    bn = min(block_n or DEFAULT_BLOCK_N, _round_up(n, 128))
    bk = min(block_k or DEFAULT_BLOCK_K, _round_up(k, 128))
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    if (mp, kp) != (m, k):
        xq = jnp.pad(xq, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        wq = jnp.pad(wq, ((0, kp - k), (0, np_ - n)))
    if np_ != n:
        sw = jnp.pad(sw, (0, np_ - n))
    sw2 = sw.reshape(1, np_).astype(jnp.float32)

    out = pl.pallas_call(
        _qmm_kernel,
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        # M/N tiles are independent; only the K walk carries the
        # accumulator (same semantics note as ops/flash_attention.py)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xq, wq, sw2)
    return out[:m, :n]
