"""Lossy compression for the stale-refresh exchanges (comm_compress).

DistriFusion's displaced-patch protocol is communication-bound at scale:
every stale step ships full-precision halo rows and KV slabs whose *only*
consumer is the next step's already-approximate stale read (tolerance-tested
at 2e-4 across the repo).  The async overlap hides that volume but does not
shrink it — so this module shrinks it: refresh payloads are quantized to 8
bits before they touch the wire and dequantized right after the collective,
with one fp32 scale per tile (the last axis: a channel vector of a halo row,
a token row of a KV slab).  The carry pytree keeps full-precision leaves —
the quantize -> collective -> dequantize round trip lives entirely on the
deferred (latency-hidden) refresh path, so the full/shallow/sync step bodies
keep identical carry structures and the step-cache / fused-scan composition
in parallel/{runner,stepcache}.py is untouched.

Modes (DistriConfig.comm_compress):

* ``"none"``          — full-precision exchange (default; bit-identical).
* ``"int8"``          — symmetric per-tile int8: ``q = round(x / s)`` with
  ``s = amax(|x|) / 127`` per tile.  Error is bounded by ``s / 2``.
* ``"fp8"``           — float8_e4m3fn payload with per-tile scaling to the
  e4m3 dynamic range (amax -> 448).  Relative error ~2^-3 of the value;
  better than int8 for heavy-tailed tiles.  Requires a jax/ml_dtypes with
  ``float8_e4m3fn`` (``fp8_supported()``).
* ``"int8_residual"`` — int8 over the *delta* against the previous stale
  value already carried in the patch state.  Adjacent denoising steps are
  near-identical, so the residual's dynamic range (and thus the per-tile
  scale, and thus the absolute error) is far smaller than the activation's.
  Closed-loop (DPCM) coding: the delta is taken against the *reconstructed*
  previous value, so quantization error does not accumulate across steps.

The same per-tile machinery also generalizes from the wires to the
*weights* (ROADMAP item 5): `QuantizedTensor` + `quantize_weight` hold
matmul/conv kernels as int8/fp8 payloads with one fp32 scale per
output-channel tile, dequantized lazily at the consuming dot/conv
(models/weights.py quantize_params owns the tree-level policy;
DistriConfig.weight_quant the knob).

Only stale-phase refresh traffic compresses; warmup/sync collectives stay
full-precision and bit-exact (reference-faithful).  GroupNorm moment
exchanges are never compressed: they are O(groups) — noise against the KV
slabs — and the ``var = E[x^2] - E[x]^2`` cancellation amplifies payload
error catastrophically.  Wire accounting for all of this lives in
``wire_nbytes`` + context.WIRE_REGISTRY, surfaced by
``DenoiseRunner.comm_volume_report(per_phase=True)["bytes"]``.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.config import SP_AXIS

COMPRESS_MODES = ("none", "int8", "fp8", "int8_residual")

# Weight-tree quantization modes (DistriConfig.weight_quant /
# weight_quant_aux; models/weights.py quantize_params).  "int8_residual" is
# wire-only: weights have no previous-step value to delta-code against.
WEIGHT_QUANT_MODES = ("none", "int8", "fp8")

# Quantized-COMPUTE policies (DistriConfig.quant_compute / ExecKey): how a
# QuantizedTensor kernel executes at its consuming matmul.  "off" is PR-6
# semantics — dequantize to the compute dtype and run a dense matmul
# (quantization buys HBM bytes, zero FLOPs).  "auto" resolves per call
# in ops/linear.py (dequant on the CPU and for a handful of tokens, the
# low-precision dot elsewhere); "dot" forces the low-precision dot_general
# path (activations dynamically quantized per token, int8/fp8 MACs, fused
# per-channel-tile scale after the accumulate).
QUANT_COMPUTE_MODES = ("off", "auto", "dot")

# Layer kinds (context.KIND_REGISTRY) whose stale refresh compresses.  "gn"
# is deliberately absent (see module docstring); "stepcache" is a local
# carry with no collective.
COMPRESS_KINDS = ("attn", "conv2d")

# int8 symmetric range and float8_e4m3fn max normal.
_INT8_MAX = 127.0
_FP8_MAX = 448.0
# Floor on per-tile scales: an all-zero tile (edge halos) must dequantize to
# exact zeros, not NaNs from a 0/0.
_SCALE_FLOOR = 1e-12


def fp8_dtype():
    """The fp8 payload dtype, or None when this jax build lacks it."""
    return getattr(jnp, "float8_e4m3fn", None)


def fp8_supported() -> bool:
    return fp8_dtype() is not None


def validate_mode(mode: str) -> None:
    """Config-time validation shared by DistriConfig and ServeConfig."""
    if mode not in COMPRESS_MODES:
        raise ValueError(
            f"comm_compress must be one of {COMPRESS_MODES}, got {mode!r}"
        )
    if mode == "fp8" and not fp8_supported():
        raise ValueError(
            "comm_compress='fp8' needs jax.numpy.float8_e4m3fn, which this "
            "jax build lacks — use 'int8' or 'int8_residual'"
        )


def quantize(x, mode: str, axis: int = -1):
    """Per-tile symmetric quantization over one reduction axis.

    Returns ``(payload, scale)``: payload is int8 (or float8_e4m3fn for
    "fp8") with x's shape; scale is fp32 with shape ``x.shape`` minus
    ``axis`` — one scale per tile.  The default ``axis=-1`` is the wire
    granularity (one scale per halo-row / KV-row); weight kernels use
    ``axis=-2`` (one scale per output-channel tile — the reduction axis of
    the consuming dot/conv, so dequantization error stays per-output-
    channel-bounded).  Exact zeros map to exact zeros (edge-device halo
    semantics depend on it).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis)
    if mode in ("int8", "int8_residual"):
        scale = jnp.maximum(amax, _SCALE_FLOOR) / _INT8_MAX
        q = jnp.clip(
            jnp.round(xf / jnp.expand_dims(scale, axis)), -_INT8_MAX,
            _INT8_MAX
        ).astype(jnp.int8)
    elif mode == "fp8":
        dt = fp8_dtype()
        if dt is None:
            raise ValueError("fp8 payloads unsupported by this jax build")
        scale = jnp.maximum(amax, _SCALE_FLOOR) / _FP8_MAX
        q = (xf / jnp.expand_dims(scale, axis)).astype(dt)
    else:
        raise ValueError(f"not a quantizing mode: {mode!r}")
    return q, scale


def dequantize(payload, scale, dtype, axis: int = -1):
    """Inverse of ``quantize`` (up to the per-tile rounding error)."""
    return (payload.astype(jnp.float32)
            * jnp.expand_dims(scale, axis)).astype(dtype)


def validate_weight_mode(mode: str) -> None:
    """Config-time validation of a weight-quantization mode, shared by
    DistriConfig (``weight_quant``/``weight_quant_aux``) and ServeConfig."""
    if mode not in WEIGHT_QUANT_MODES:
        raise ValueError(
            f"weight_quant must be one of {WEIGHT_QUANT_MODES}, got {mode!r}"
        )
    if mode == "fp8" and not fp8_supported():
        raise ValueError(
            "weight_quant='fp8' needs jax.numpy.float8_e4m3fn, which this "
            "jax build lacks — use 'int8'"
        )


def validate_quant_compute(policy: str, weight_quant: str = "int8") -> None:
    """Config-time validation of a quantized-compute policy, shared by
    DistriConfig, ServeConfig, and ExecKey.  Forcing a low-precision
    execution path ("dot") on a full-precision key is a config
    contradiction — there is no quantized kernel to execute — and refuses
    loudly rather than silently running dense."""
    if policy not in QUANT_COMPUTE_MODES:
        raise ValueError(
            f"quant_compute must be one of {QUANT_COMPUTE_MODES}, got "
            f"{policy!r}"
        )
    if policy == "dot" and weight_quant == "none":
        raise ValueError(
            f"quant_compute={policy!r} forces a low-precision matmul path "
            "but weight_quant='none' holds no quantized kernels — set "
            "weight_quant to int8/fp8 or keep quant_compute 'auto'/'off'"
        )


@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """A quantized weight kernel: 1-byte payload + one fp32 scale per
    output-channel tile, dequantized lazily where it is consumed.

    The payload keeps the kernel's layout (linear ``[..., in, out]``, conv
    HWIO ``[kh, kw, I, O]``); the scale reduces away the second-to-last
    (input/reduction) axis, so a stacked block tree ``[depth, in, out]``
    keeps per-(block, out-channel) scales and slices along ``depth``
    exactly like a dense leaf (``jax.tree.map(lambda l: l[:k], ...)``
    maps into payload and scale, both depth-leading).

    Registered as a pytree node, so quantized trees flow through jit /
    shard_map / scan unchanged; ``__jax_array__`` makes any jnp consumer
    (``x @ kernel``, einsum, vmap'd linears) dequantize on the fly —
    inside a traced program XLA fuses the convert+multiply into the
    consuming dot, so HBM holds (and streams) the 1-byte payload.  lax
    primitives don't take the protocol: explicit call sites (the conv
    paths in ops/conv.py) densify via ``asdense``.

    ``compute`` is the EXECUTION policy (QUANT_COMPUTE_MODES minus "off",
    which maps to the leaf-level "dequant"): ops/linear.py dispatches a
    QuantizedTensor kernel to the low-precision dot_general or the
    dequantized dense matmul per this policy.  It lives in the
    pytree AUX data (not a traced leaf), so two trees differing only in
    policy have distinct treedefs — jit retraces instead of silently
    reusing the other policy's program.  ``channel_tile`` groups output
    channels per scale (1 = per-channel, the default and the PR-6
    layout); the scale's last axis then has ``ceil(out/channel_tile)``
    entries, with a partial last tile when out %% channel_tile != 0.
    """

    __slots__ = ("payload", "scale", "_dtype", "compute", "channel_tile")

    def __init__(self, payload, scale, dtype, compute: str = "dequant",
                 channel_tile: int = 1):
        self.payload = payload
        self.scale = scale
        self._dtype = jnp.dtype(dtype)
        if compute not in ("dequant", "auto", "dot"):
            raise ValueError(
                f"QuantizedTensor compute policy must be 'dequant', "
                f"'auto', or 'dot', got {compute!r}"
            )
        self.compute = compute
        ct = int(channel_tile)
        if ct < 1:
            raise ValueError(f"channel_tile must be >= 1, got {channel_tile}")
        n = payload.shape[-1] if getattr(payload, "ndim", 0) else 1
        tiles = -(-n // ct)
        sl = scale.shape[-1] if getattr(scale, "ndim", 0) else 1
        if sl != tiles:
            raise ValueError(
                f"scale/payload tile misalignment: payload has {n} output "
                f"channels at channel_tile={ct} -> {tiles} scale tiles, "
                f"but the scale's last axis has {sl} — a round-trip that "
                "dropped the tile size would dequantize with the wrong "
                "per-channel scales"
            )
        self.channel_tile = ct

    @property
    def shape(self):
        return self.payload.shape

    @property
    def ndim(self) -> int:
        return self.payload.ndim

    @property
    def size(self) -> int:
        return self.payload.size

    @property
    def dtype(self):
        """The dequantized (compute) dtype — what the dense leaf had."""
        return self._dtype

    @property
    def nbytes(self) -> int:
        """HBM residency: payload plus scales (what the fleet's weight
        reports sum)."""
        return int(self.payload.size * jnp.dtype(self.payload.dtype).itemsize
                   + self.scale.size * 4)

    def channel_scale(self):
        """The fp32 scale EXPANDED to one entry per output channel
        ([..., out]), regardless of ``channel_tile`` — what the fused
        scale application after a low-precision accumulate multiplies by
        (and what ``__jax_array__`` dequantizes with)."""
        if self.channel_tile == 1:
            return self.scale
        n = self.payload.shape[-1]
        return jnp.repeat(self.scale, self.channel_tile, axis=-1)[..., :n]

    def __jax_array__(self):
        return dequantize(self.payload, self.channel_scale(), self._dtype,
                          axis=-2)

    def __repr__(self) -> str:
        return (f"QuantizedTensor(shape={tuple(self.shape)}, "
                f"payload={jnp.dtype(self.payload.dtype).name}, "
                f"dtype={self._dtype.name}, compute={self.compute!r}, "
                f"channel_tile={self.channel_tile})")

    def tree_flatten(self):
        return ((self.payload, self.scale),
                (self._dtype, self.compute, self.channel_tile))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)


def quantize_weight(w, mode: str, *, compute: str = "dequant",
                    channel_tile: int = 1) -> QuantizedTensor:
    """Quantize one kernel leaf with per-output-channel-tile fp32 scales
    (the output axis is last in both the linear and HWIO conv layouts, so
    the reduction axis is always ``-2``).  ``channel_tile > 1`` groups
    that many output channels per scale (each tile's scale is the max of
    its channels' amax, so the per-element error bound still holds — just
    against the tile amax, which is why per-channel stays the default);
    the last tile is partial when the channel count does not divide.
    ``compute`` tags the execution policy (see QuantizedTensor)."""
    if mode not in ("int8", "fp8"):
        raise ValueError(f"not a weight-quantizing mode: {mode!r}")
    ct = int(channel_tile)
    if ct <= 1:
        q, scale = quantize(w, mode, axis=-2)
        return QuantizedTensor(q, scale, w.dtype, compute, 1)
    xf = jnp.asarray(w).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-2)  # [..., out] per-channel amax
    n = amax.shape[-1]
    tiles = -(-n // ct)
    pad = tiles * ct - n
    if pad:
        # pad with 0 so a partial last tile's scale is the max of its REAL
        # channels only
        amax = jnp.pad(amax, [(0, 0)] * (amax.ndim - 1) + [(0, pad)])
    tile_amax = amax.reshape(*amax.shape[:-1], tiles, ct).max(axis=-1)
    limit = _INT8_MAX if mode == "int8" else _FP8_MAX
    scale = jnp.maximum(tile_amax, _SCALE_FLOOR) / limit
    per_ch = jnp.repeat(scale, ct, axis=-1)[..., :n]
    div = xf / jnp.expand_dims(per_ch, -2)
    if mode == "int8":
        q = jnp.clip(jnp.round(div), -_INT8_MAX, _INT8_MAX).astype(jnp.int8)
    else:
        q = div.astype(fp8_dtype())
    return QuantizedTensor(q, scale, w.dtype, compute, ct)


def asdense(x):
    """Dequantize a `QuantizedTensor` (identity on anything else) — for
    call sites that feed lax primitives directly, which don't take the
    ``__jax_array__`` protocol."""
    return x.__jax_array__() if isinstance(x, QuantizedTensor) else x


def refresh_period(fraction: float) -> int:
    """``1 / fraction`` as the exact integer rotation period of the
    partial-refresh schedule (1 when the fraction is 1.0 — full refresh).
    ``validate_refresh_fraction`` guarantees the division is exact."""
    return int(round(1.0 / float(fraction)))


def validate_refresh_fraction(fraction: float) -> None:
    """Config-time validation of a PCPP partial-refresh fraction, shared
    by DistriConfig, ServeConfig, ExecKey, and the controller tier table.

    The fraction must be ``1/k`` for an integer ``k >= 1``: each stale
    step refreshes exactly one of ``k`` disjoint strided row groups, so
    the per-step wire bytes are exactly ``fraction`` of the full refresh
    and every row is at most ``k`` steps stale — both closed forms the
    byte accounting and the staleness bound depend on being exact."""
    f = float(fraction)
    if not (0.0 < f <= 1.0):
        raise ValueError(
            f"refresh_fraction must be in (0, 1], got {fraction!r}"
        )
    k = round(1.0 / f)
    if k < 1 or abs(k * f - 1.0) > 1e-6:
        raise ValueError(
            "refresh_fraction must be 1/k for an integer k (1, 0.5, 0.25, "
            f"...): each stale step refreshes one of k strided row groups "
            f"exactly — got {fraction!r}"
        )


def take_every_kth(x, k: int, r, *, groups: int = 1):
    """Strided row subset along axis ``-2``: rows ``{r, r+k, r+2k, ...}``
    of each of ``groups`` equal contiguous segments (static output shape
    ``[..., L/k, C]``; ``r`` may be a traced index).

    ``groups > 1`` handles a tiled-all-gather layout where axis ``-2``
    concatenates per-device chunks: the stride applies within each
    device's chunk, not across the concatenation boundary."""
    lead, L, C = x.shape[:-2], x.shape[-2], x.shape[-1]
    if L % (groups * k):
        raise ValueError(
            f"partial refresh needs the row count ({L}) divisible by "
            f"groups*k ({groups}*{k}) — pick a refresh_fraction whose "
            "period divides every refreshed row dimension"
        )
    xg = x.reshape(*lead, groups, L // (groups * k), k, C)
    sub = lax.dynamic_index_in_dim(xg, r, axis=xg.ndim - 2, keepdims=False)
    return sub.reshape(*lead, L // k, C)


def scatter_every_kth(prev, rows, k: int, r, *, groups: int = 1):
    """Inverse of `take_every_kth`: write ``rows`` [..., L/k, C] back into
    the strided positions of ``prev`` [..., L, C] (same ``groups``
    convention), returning the updated full buffer in prev's dtype."""
    lead, L, C = prev.shape[:-2], prev.shape[-2], prev.shape[-1]
    pg = prev.reshape(*lead, groups, L // (groups * k), k, C)
    up = rows.reshape(*lead, groups, L // (groups * k), 1, C)
    pg = lax.dynamic_update_slice_in_dim(
        pg, up.astype(prev.dtype), r, axis=pg.ndim - 2
    )
    return pg.reshape(prev.shape)


def wire_nbytes(shape: Sequence[int], itemsize: int, mode: str) -> int:
    """Bytes one exchange of a ``shape``-shaped tensor puts on the wire.

    ``"none"`` moves the raw payload; the quantizing modes move a 1-byte
    payload per element plus one fp32 scale per tile (last-axis vector).
    The comm accounting's single source of truth — context.WIRE_REGISTRY
    entries and the closed-form DiT/MMDiT reports both come from here.
    """
    n = int(math.prod(shape))
    if mode == "none":
        return n * itemsize
    tiles = int(math.prod(shape[:-1])) if len(shape) else 1
    return n + tiles * 4


def refresh_gather_seq(
    local,
    prev,
    mode: str,
    offset,
    axis: str = SP_AXIS,
    *,
    fraction: float = 1.0,
    step=None,
):
    """Compressed sequence-sharded refresh all-gather (DiT/MMDiT KV path).

    ``local`` is this device's fresh stacked KV rows ``[2, B, chunk, hid]``;
    ``prev`` the previous step's gathered state ``[2, B, N, hid]`` (the scan
    carry).  Returns the refreshed full ``[2, B, N, hid]`` in prev's dtype:
    a plain tiled all-gather for "none", a quantized payload + per-row fp32
    scale pair of gathers otherwise, with "int8_residual" delta-coding
    against this device's own slice of ``prev`` at token offset ``offset``.
    The result is consumed only next step, so every op here stays on the
    deferred path.

    ``fraction < 1`` is the PCPP partial-refresh path (arXiv 2412.02962):
    with period ``k = 1/fraction``, step ``step`` refreshes only rows
    ``{r, r+k, ...}`` (``r = step % k``) of each device's chunk — the
    all-gather moves ``chunk/k`` rows per device, the rest of ``prev``
    carries, and every row is at most ``k`` steps stale.  The rotation
    index is shared by every device (``step`` is replicated), so the
    refreshed gathered buffer stays replicated-consistent, and in
    residual mode the delta base is the row's own ``k``-step-old
    reconstruction — still closed-loop DPCM, just at stride ``k``."""
    tok = local.ndim - 2  # token axis of the [..., chunk, hid] layout
    k = refresh_period(fraction)
    if k <= 1:
        if mode == "none":
            return lax.all_gather(local, axis, axis=tok, tiled=True)
        src = local.astype(jnp.float32)
        if mode == "int8_residual":
            start = (0,) * tok + (offset, 0)
            my_prev = lax.dynamic_slice(prev, start, local.shape)
            src = src - my_prev.astype(jnp.float32)
        q, s = quantize(src, mode)
        gq = lax.all_gather(q, axis, axis=tok, tiled=True)
        gs = lax.all_gather(s, axis, axis=tok, tiled=True)
        new = gq.astype(jnp.float32) * gs[..., None]
        if mode == "int8_residual":
            new = prev.astype(jnp.float32) + new
        return new.astype(prev.dtype)
    if step is None:
        raise ValueError(
            "partial refresh (fraction < 1) needs the traced step index "
            "for the rotation schedule"
        )
    n = prev.shape[tok] // local.shape[tok]  # sp peers in the gathered axis
    r = jnp.mod(jnp.asarray(step, jnp.int32), k)
    sub = take_every_kth(local, k, r)  # [2, B, chunk/k, hid]
    if mode == "none":
        g = lax.all_gather(sub, axis, axis=tok, tiled=True)
        return scatter_every_kth(prev, g, k, r, groups=n)
    src = sub.astype(jnp.float32)
    if mode == "int8_residual":
        start = (0,) * tok + (offset, 0)
        my_prev = lax.dynamic_slice(prev, start, local.shape)
        src = src - take_every_kth(my_prev, k, r).astype(jnp.float32)
    q, s = quantize(src, mode)
    gq = lax.all_gather(q, axis, axis=tok, tiled=True)
    gs = lax.all_gather(s, axis, axis=tok, tiled=True)
    new = gq.astype(jnp.float32) * gs[..., None]
    if mode == "int8_residual":
        new = take_every_kth(prev, k, r, groups=n).astype(jnp.float32) + new
    return scatter_every_kth(prev, new, k, r, groups=n)
