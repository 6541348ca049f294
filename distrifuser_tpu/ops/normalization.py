"""GroupNorm: dense + the six-mode distributed variant.

TPU-native re-design of the reference's `DistriGroupNorm`
(/root/reference/distrifuser/modules/pp/groupnorm.py).  On a row-sharded
activation the group statistics need cross-device reduction; the reference
implements six sync modes (SURVEY.md §2.8) which we reproduce exactly,
including two deliberate numerical quirks that the quality ablations in the
paper depend on:

* the distributed paths apply a Bessel factor ``ne/(ne-1)`` with the *local*
  element count (groupnorm.py:65-66,84-85), while plain GroupNorm (torch and
  our dense version) uses the biased variance;
* ``corrected_async_gn`` adds the freshness correction
  ``local_fresh - local_stale`` un-normalized (not divided by n,
  groupnorm.py:49-51), and falls back to the local variance wherever the
  corrected variance goes negative (groupnorm.py:60-63).

Moments are accumulated in fp32 (the reference inherits fp16 accumulation
from torch; bf16 has fewer mantissa bits, so fp32 accumulation is load-bearing
for PSNR parity).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.collectives import all_gather, psum_mean
from ..parallel.context import PatchContext


def _affine(p, y):
    if p is not None and "scale" in p:
        y = y * p["scale"]
        if "bias" in p:
            y = y + p["bias"]
    return y


def _normalize_rows(xg, eps: float):
    """Each of the b > 1 rows of ``xg`` (float32 ``[b, h, w, groups, c /
    groups]``) by its own moments, with no reduction and no broadcast that
    runs along part of the batch axis.

    Row i's mean and mean of squares are reductions through the batch axis
    too, the other rows selected to zero; every row is normalised by row i's
    moments (``[groups]``, nothing of b in their shape) and row i's result is
    selected.  The TPU compiler folds the rows of a conv's output into its
    blocks of W (``[H, b * 8, W / 8, C]``) and carries that form from conv to
    conv.  A moment per row taken over ``axis=(1, 2, 4)`` cuts through the
    folded axis: the compiler then wrote the activation out in float32 and
    each moment as a broadcast of that size (4.7 GB of HBM traffic a guided
    SDXL step), and rows sliced apart and concatenated made the convs behind
    them leave the folded form.  This way the moments ride out of the
    producing conv as they do at one row, and normalising is one read and one
    write (PERF.md section 6, PR 30).

    The variance is ``E[x^2] - mean^2`` in float32, floored at zero, so that
    both moments come of the one read; at a mean of 10 standard deviations
    the result still holds to 1e-3.
    """
    b, h, w, _, per_group = xg.shape
    n = h * w * per_group
    row = lax.broadcasted_iota(jnp.int32, (b, 1, 1, 1, 1), 0)

    def mean_over_row(i, v):
        return jnp.where(row == i, v, 0.0).sum(axis=(0, 1, 2, 4), keepdims=True) / n

    y = None
    for i in range(b):
        mean = mean_over_row(i, xg)
        var = jnp.maximum(mean_over_row(i, jnp.square(xg)) - jnp.square(mean), 0.0)
        y_i = (xg - mean) * lax.rsqrt(var + eps)
        y = y_i if y is None else jnp.where(row == i, y_i, y)
    return y


@jax.named_scope("groupnorm")
def group_norm(p, x, *, groups: int, eps: float = 1e-5):
    """Dense GroupNorm over NHWC, biased variance (torch nn.GroupNorm semantics)."""
    b, h, w, c = x.shape
    xg = x.reshape(b, h, w, groups, c // groups).astype(jnp.float32)
    if b > 1:
        y = _normalize_rows(xg, eps)
    else:
        mean = xg.mean(axis=(1, 2, 4), keepdims=True)
        var = jnp.square(xg - mean).mean(axis=(1, 2, 4), keepdims=True)
        y = (xg - mean) * lax.rsqrt(var + eps)
    y = y.reshape(b, h, w, c).astype(x.dtype)
    return _affine(p, y)


def _local_moments(x, groups: int):
    """Per-group local E[x], E[x^2]: fp32 [2, B, G] (groupnorm.py:38-41)."""
    b, h, w, c = x.shape
    xg = x.reshape(b, h, w, groups, c // groups).astype(jnp.float32)
    m1 = xg.mean(axis=(1, 2, 4))
    m2 = jnp.square(xg).mean(axis=(1, 2, 4))
    return jnp.stack([m1, m2])


def _normalize(p, x, full_mean, var, *, groups: int, eps: float, bessel_ne: int):
    """Shared tail: Bessel-correct, rsqrt, affine (groupnorm.py:65-72)."""
    b, h, w, c = x.shape
    var = var * (bessel_ne / (bessel_ne - 1))
    std_inv = lax.rsqrt(var + eps)  # [2?, B, G] -> broadcast over pixels
    xg = x.reshape(b, h, w, groups, c // groups).astype(jnp.float32)
    mean_b = full_mean[:, None, None, :, None]  # [B,1,1,G,1]
    std_b = std_inv[:, None, None, :, None]
    y = ((xg - mean_b) * std_b).reshape(b, h, w, c).astype(x.dtype)
    return _affine(p, y)


@jax.named_scope("groupnorm")
def patch_group_norm(
    p, x, ctx: PatchContext, name: str, *, groups: int, eps: float = 1e-5
):
    """Distributed GroupNorm on a row-sharded [B, h_local, W, C] activation."""
    if ctx.n == 1:
        return group_norm(p, x, groups=groups, eps=eps)
    b, h, w, c = x.shape
    ne = (c // groups) * h * w  # local element count (reference Bessel basis)

    if ctx.mode in ("stale_gn", "corrected_async_gn"):
        m = _local_moments(x, groups)  # [2, B, G]
        if ctx.is_sync:
            with jax.named_scope("gn_stats"):
                gathered = all_gather(m, ctx.axis)  # [n, 2, B, G]
                ctx.emit(name, gathered, kind="gn")
            full = gathered.mean(axis=0)
        else:
            gathered = ctx.stale(name)
            idx = ctx.split_idx()
            own_stale = jnp.take(gathered, idx, axis=0)
            if ctx.mode == "corrected_async_gn":
                # stale global mean + un-normalized freshness correction
                # (groupnorm.py:49-51)
                full = gathered.mean(axis=0) + (m - own_stale)
            else:  # stale_gn: stale peers + fresh self (groupnorm.py:52-55)
                full = (gathered.sum(axis=0) - own_stale + m) / ctx.n
            with jax.named_scope("gn_stats"):
                ctx.emit_refresh_gather(name, m, kind="gn")
        var = full[1] - jnp.square(full[0])
        if ctx.mode == "corrected_async_gn":
            local_var = m[1] - jnp.square(m[0])
            var = jnp.where(var < 0, local_var, var)  # groupnorm.py:60-63
        return _normalize(p, x, full[0], var, groups=groups, eps=eps, bessel_ne=ne)

    if ctx.is_sync or ctx.mode == "sync_gn":
        # Blocking all_reduce of moments every step (groupnorm.py:74-91);
        # also the warmup path for separate_gn / no_sync.
        m = _local_moments(x, groups)
        with jax.named_scope("gn_stats"):
            full = psum_mean(m, ctx.axis)
        var = full[1] - jnp.square(full[0])
        return _normalize(p, x, full[0], var, groups=groups, eps=eps, bessel_ne=ne)

    # separate_gn / no_sync steady state: purely local GN, no Bessel
    # (groupnorm.py:92-93 falls back to the unwrapped nn.GroupNorm).
    return group_norm(p, x, groups=groups, eps=eps)
