"""Convolutions: dense NHWC conv + the two patch-parallel variants.

TPU-native re-design of the reference's `DistriConv2dPP`
(/root/reference/distrifuser/modules/pp/conv2d.py):

* `conv2d` — plain XLA conv (`lax.conv_general_dilated`, NHWC/HWIO), the
  cuDNN `F.conv2d` equivalent.
* `sliced_conv2d` — the first-layer path (`sliced_forward`, conv2d.py:20-41):
  every device holds the *full* input and computes only its own output rows.
  The reference clamps the slice at image edges and pads conditionally; we
  zero-pad the full input once and take a uniform-size dynamic slice, which
  keeps shapes static for SPMD and reproduces the same edge zeros.
* `patch_conv2d` — the halo-exchange path (conv2d.py:43-115): row-sharded
  activations, k>1 convs need `padding` boundary rows from each spatial
  neighbor.  Sync phase exchanges fresh halos (reference warmup all_gather,
  conv2d.py:92-101); stale phase computes with the previous step's halos from
  the carry state and exchanges fresh ones for the next step (the async
  enqueue, conv2d.py:102-112).  Halos move via `lax.ppermute` between
  neighbors only — the reference gathers every peer's boundary to every rank
  but reads just the two neighbors.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.compress import asdense
from ..parallel.context import PatchContext

_DIMNUMS = ("NHWC", "HWIO", "NHWC")


@jax.named_scope("conv")
def conv2d(p, x, *, stride: int = 1, padding=None):
    """Dense NHWC conv. `padding` defaults to (k-1)//2 ("same" for odd k).

    ``asdense`` dequantizes a weight-quantized kernel right here, at the
    consuming conv (lax primitives don't take ``__jax_array__``); inside a
    traced program XLA fuses the convert, so HBM still holds the int8/fp8
    payload."""
    kh, kw = p["kernel"].shape[:2]
    if padding is None:
        padding = ((kh - 1) // 2, (kw - 1) // 2)
    elif isinstance(padding, int):
        padding = (padding, padding)
    y = lax.conv_general_dilated(
        x,
        asdense(p["kernel"]),
        window_strides=(stride, stride),
        padding=(
            (padding[0], padding[0]),
            (padding[1], padding[1]),
        ),
        dimension_numbers=_DIMNUMS,
    )
    if "bias" in p:
        y = y + p["bias"]
    return y


@jax.named_scope("conv")
def _conv_valid_h(p, x, stride: int, pad_w: int):
    """Conv with height padding already materialized in `x` (halo rows), width
    padded normally — the reference's F.conv2d(..., padding=(0, pad_w))
    (conv2d.py:95-110)."""
    y = lax.conv_general_dilated(
        x,
        asdense(p["kernel"]),
        window_strides=(stride, stride),
        padding=((0, 0), (pad_w, pad_w)),
        dimension_numbers=_DIMNUMS,
    )
    if "bias" in p:
        y = y + p["bias"]
    return y


def sliced_conv2d(p, x_full, ctx: PatchContext, *, stride: int = 1):
    """First-layer conv (`conv_in`): full input, my output rows only.

    Mirrors sliced_forward (conv2d.py:20-41): output rows
    ``[out_h_local*idx, out_h_local*(idx+1))`` need input rows
    ``[idx*out_h_local*stride - pad, (idx+1)*out_h_local*stride + pad)``;
    zero-padding the full input first makes the slice uniform across devices
    and supplies the image-border zeros.
    """
    kh, kw = p["kernel"].shape[:2]
    ph, pw = (kh - 1) // 2, (kw - 1) // 2
    b, h, w, c = x_full.shape
    assert h % (stride * ctx.n) == 0, f"input height {h} not divisible by stride*n"
    out_h_local = h // stride // ctx.n
    xp = jnp.pad(x_full, ((0, 0), (ph, ph), (0, 0), (0, 0)))
    start = ctx.split_idx() * out_h_local * stride  # in padded coords
    sl = lax.dynamic_slice_in_dim(xp, start, out_h_local * stride + 2 * ph, axis=1)
    return _conv_valid_h(p, sl, stride, pw)


def patch_conv2d(p, x, ctx: PatchContext, name: str, *, stride: int = 1):
    """Halo conv on a row-sharded activation [B, h_local, W, C]."""
    kh, kw = p["kernel"].shape[:2]
    ph, pw = (kh - 1) // 2, (kw - 1) // 2
    if ctx.n == 1 or ph == 0:
        # 1xk kernels need no row halo; the reference leaves 1x1 convs
        # unwrapped entirely (distri_sdxl_unet_pp.py:24-26).
        return conv2d(p, x, stride=stride, padding=(ph, pw))

    with jax.named_scope("halo"):
        if ctx.is_sync:
            # Fresh halos double as the seed state for the stale phase; the
            # context hook also seeds the own-rows carry residual
            # compression delta-codes against (parallel/compress.py).
            top, bottom = ctx.emit_sync_halos(name, x, ph)
        else:
            halos = ctx.stale(name)  # [2, B, ph, W, C], the previous step's
            top, bottom = halos[0], halos[1]
            if ctx.refresh:
                ctx.emit_refresh_halos(name, x, ph)
        padded = jnp.concatenate([top, x, bottom], axis=1)
    return _conv_valid_h(p, padded, stride, pw)
