"""AutoencoderKL (the SD/SDXL VAE) in JAX.

The reference uses the diffusers VAE unchanged and runs the decode replicated
on the full gathered latent on every rank (SURVEY.md §1,
/root/reference/distrifuser/pipelines.py:39-42).  Here the decoder is also
**sequence-parallel** (`decode_sp`, beyond the reference): row-sharded over
the same `sp` mesh axis as the UNet, with fresh halo-exchange convs, psum'd
GroupNorm moments, and an exact ring attention for the mid block — no
staleness anywhere, so the distributed decode is numerically the dense
decode, n× faster and with 1/n the activation footprint (what makes 3840²
fit without serial tiling).  Decoder + encoder, diffusers-0.24
architecture: resnets without time embedding, a single-head mid-block
attention, nearest-2x upsampling.

For single-device runs at very large sizes, `decode(..., tile=N)` decodes in
latent-space row tiles with overlap blending (the diffusers enable_tiling
analog) so 3840x3840 outputs fit on one chip.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import sdpa
from ..parallel.collectives import psum_mean
from ..ops.conv import _conv_valid_h, conv2d
from ..ops.linear import linear
from ..ops.normalization import _local_moments, group_norm
from ..ops.ring_attention import ring_pass
from ..parallel.collectives import halo_exchange
from ..utils.config import SP_AXIS

silu = jax.nn.silu


@dataclasses.dataclass(frozen=True)
class VAEConfig:
    in_channels: int = 3
    out_channels: int = 3
    latent_channels: int = 4
    block_out_channels: Tuple[int, ...] = (128, 256, 512, 512)
    layers_per_block: int = 2
    norm_num_groups: int = 32
    scaling_factor: float = 0.13025  # SDXL; SD 1.x uses 0.18215
    # SD3-family VAEs re-center the latent: x = latent / scaling + shift
    # before decode (and (x - shift) * scaling after encode); 0.0 for
    # SD 1.x/2.x/SDXL keeps the legacy formula untouched
    shift_factor: float = 0.0


def sdxl_vae_config() -> VAEConfig:
    return VAEConfig()


def sd_vae_config() -> VAEConfig:
    return VAEConfig(scaling_factor=0.18215)


def vae_config_from_json(source) -> VAEConfig:
    """Build a VAEConfig from a diffusers `vae/config.json` (path or dict) —
    carries the snapshot's true scaling_factor (0.18215 SD, 0.13025 SDXL)
    and channel layout instead of assuming a preset."""
    from .unet import load_config_source

    cfg = load_config_source(source)
    return VAEConfig(
        in_channels=cfg.get("in_channels", 3),
        out_channels=cfg.get("out_channels", 3),
        latent_channels=cfg.get("latent_channels", 4),
        block_out_channels=tuple(cfg.get("block_out_channels", (128, 256, 512, 512))),
        layers_per_block=cfg.get("layers_per_block", 2),
        norm_num_groups=cfg.get("norm_num_groups", 32),
        scaling_factor=cfg.get("scaling_factor", 0.18215),
        shift_factor=cfg.get("shift_factor") or 0.0,
    )


def tiny_vae_config() -> VAEConfig:
    return VAEConfig(block_out_channels=(16, 32), layers_per_block=1,
                     norm_num_groups=8, scaling_factor=0.18215)


def _vae_resnet(p, x, groups):
    h = conv2d(p["conv1"], silu(group_norm(p["norm1"], x, groups=groups, eps=1e-6)))
    h = conv2d(p["conv2"], silu(group_norm(p["norm2"], h, groups=groups, eps=1e-6)))
    if "conv_shortcut" in p:
        x = conv2d(p["conv_shortcut"], x)
    return x + h


def _vae_attention(p, x, groups):
    b, h, w, c = x.shape
    hs = group_norm(p["group_norm"], x, groups=groups, eps=1e-6).reshape(b, h * w, c)
    q = linear(p["to_q"], hs)
    k = linear(p["to_k"], hs)
    v = linear(p["to_v"], hs)
    out = sdpa(q, k, v, heads=1)
    out = linear(p["to_out"], out).reshape(b, h, w, c)
    return x + out


@jax.named_scope("vae_decode")
def decode(params, cfg: VAEConfig, latents, *, tile: int = 0):
    """Latent [B, h, w, 4] (already divided by scaling_factor) -> image
    [B, 8h, 8w, 3] in [-1, 1].  ``tile``: latent rows per tile (0 = whole).

    One decoder topology serves both execution modes: this dense path is
    ``decode_sp`` at n == 1 (every _sp helper degenerates to its dense op),
    so the sp exactness contract can't drift from the architecture."""
    if tile and latents.shape[1] > tile:
        return _decode_tiled(params, cfg, latents, tile)
    return decode_sp(params, cfg, latents, 1)


def _decode_tiled(params, cfg, latents, tile: int, overlap: int = 8):
    """Row-tiled decode with linear blending in the overlaps — the
    diffusers enable_tiling analog for single-chip 4K decodes.  All tiles
    share one shape so XLA compiles the decoder once."""
    b, h, w, c = latents.shape
    scale = 1 << (len(cfg.block_out_channels) - 1)  # latent row -> pixel rows
    overlap = min(overlap, tile // 2)
    stride = tile - overlap
    starts = list(range(0, h - tile, stride)) + [h - tile]
    pieces = [decode(params, cfg, latents[:, s : s + tile], tile=0) for s in starts]

    rows = []
    for i, s in enumerate(starts):
        piece = pieces[i]
        if i > 0:
            ov = (starts[i - 1] + tile - s) * scale  # pixel rows shared w/ prev
            blend = jnp.linspace(0.0, 1.0, ov)[None, :, None, None]
            prev_tail = pieces[i - 1][:, -ov:]
            piece = piece.at[:, :ov].set(prev_tail * (1 - blend) + piece[:, :ov] * blend)
        keep_rows = (
            (starts[i + 1] - s) * scale if i + 1 < len(starts) else tile * scale
        )
        rows.append(piece[:, :keep_rows])
    return jnp.concatenate(rows, axis=1)


# ---------------------------------------------------------------------------
# sequence-parallel decode (exact; runs inside shard_map over the sp axis)
# ---------------------------------------------------------------------------


def _conv_sp(p, x, n, axis):
    """3x3 (or 1x1) conv on a row-sharded [B, h_local, W, C] activation with
    FRESH neighbor halos — unlike the UNet's displaced patch conv there is no
    denoising loop here, so halos are exchanged synchronously and the result
    is exactly the dense conv."""
    kh, kw = p["kernel"].shape[:2]
    ph, pw = (kh - 1) // 2, (kw - 1) // 2
    if ph == 0 or n == 1:
        return conv2d(p, x)
    top, bottom = halo_exchange(x, ph, n, axis)
    return _conv_valid_h(p, jnp.concatenate([top, x, bottom], axis=1), 1, pw)


def _group_norm_sp(p, x, n, axis, *, groups, eps):
    """Exact distributed GroupNorm: pmean'd fp32 moments, biased variance
    (plain torch nn.GroupNorm semantics — no Bessel quirk here; that belongs
    to the reference's UNet DistriGroupNorm only)."""
    if n == 1:
        return group_norm(p, x, groups=groups, eps=eps)
    b, h, w, c = x.shape
    m = psum_mean(_local_moments(x, groups), axis)  # [2, B, G], equal shards
    # clamp: E[x^2]-E[x]^2 can go slightly negative from fp32 cancellation
    # (the dense path's two-pass formula is non-negative by construction)
    mean, var = m[0], jnp.maximum(m[1] - jnp.square(m[0]), 0.0)
    xg = x.reshape(b, h, w, groups, c // groups).astype(jnp.float32)
    y = (xg - mean[:, None, None, :, None]) * lax.rsqrt(
        var[:, None, None, :, None] + eps
    )
    y = y.reshape(b, h, w, c).astype(x.dtype)
    if p is not None and "scale" in p:
        y = y * p["scale"]
        if "bias" in p:
            y = y + p["bias"]
    return y


# max fp32 logit elements per ring hop (L_loc_q x L_loc_k); above this the
# query rows are processed in sequential chunks, each running its own ring —
# q rows are independent in attention, so this is exact (same safety net as
# ops.attention.sdpa's _CHUNK_LOGITS_ELEMS, sized for the ~230k-token mid
# attention of a 3840^2 decode)
_SP_CHUNK_LOGITS_ELEMS = 1 << 27


def _vae_attention_sp(p, x, n, axis, groups):
    """Mid-block attention over the full (row-sharded) token sequence via an
    exact ring: every chunk is fresh, merged with the flash-style online
    softmax, so the output equals full dense attention while holding only
    O(L/n) keys/values per device."""
    if n == 1:
        return _vae_attention(p, x, groups)
    b, h, w, c = x.shape
    l_loc = h * w
    hs = _group_norm_sp(
        p["group_norm"], x, n, axis, groups=groups, eps=1e-6
    ).reshape(b, l_loc, c)
    q = linear(p["to_q"], hs)
    kv = jnp.concatenate([linear(p["to_k"], hs), linear(p["to_v"], hs)], axis=-1)

    def ring(q_rows):
        """Full exact ring pass for an independent block of query rows."""
        out = ring_pass(q_rows, kv, kv, n, axis, heads=1)
        return out.astype(x.dtype)[:, 0]  # single head

    if b * l_loc * l_loc <= _SP_CHUNK_LOGITS_ELEMS or l_loc == 1:
        out = ring(q)
    else:
        n_chunks = 1
        while b * (l_loc // n_chunks) * l_loc > _SP_CHUNK_LOGITS_ELEMS and n_chunks < l_loc:
            n_chunks *= 2
        lq_pad = -(-l_loc // n_chunks) * n_chunks
        qp = jnp.pad(q, ((0, 0), (0, lq_pad - l_loc), (0, 0)))
        qc = jnp.moveaxis(qp.reshape(b, n_chunks, lq_pad // n_chunks, c), 1, 0)
        out = lax.map(ring, qc)  # sequential chunks, bounded logits
        out = jnp.moveaxis(out, 0, 1).reshape(b, lq_pad, c)[:, :l_loc]
    out = linear(p["to_out"], out).reshape(b, h, w, c)
    return x + out


def _vae_resnet_sp(p, x, n, axis, groups):
    h = _conv_sp(
        p["conv1"], silu(_group_norm_sp(p["norm1"], x, n, axis, groups=groups, eps=1e-6)),
        n, axis,
    )
    h = _conv_sp(
        p["conv2"], silu(_group_norm_sp(p["norm2"], h, n, axis, groups=groups, eps=1e-6)),
        n, axis,
    )
    if "conv_shortcut" in p:
        x = conv2d(p["conv_shortcut"], x)  # 1x1: local
    return x + h


@jax.named_scope("vae_decode")
def decode_sp(params, cfg: VAEConfig, latents, n: int, axis: str = SP_AXIS):
    """Sequence-parallel decode (beyond the reference, which decodes the full
    latent replicated on every rank — pipelines.py:39-42 there).

    ``latents``: this device's latent row shard [B, h/n, w, 4] (already
    divided by scaling_factor), inside `shard_map` with ``axis`` bound.
    Returns this device's pixel rows [B, 8h/n, w, 3].  Exact: fresh halo
    convs + pmean GroupNorm + ring mid attention — bit-level parity with
    `decode` is pinned by tests/test_vae_sp.py.
    """
    p = params["decoder"]
    groups = cfg.norm_num_groups
    latents = latents.astype(params["post_quant_conv"]["kernel"].dtype)
    x = conv2d(params["post_quant_conv"], latents)
    x = _conv_sp(p["conv_in"], x, n, axis)
    x = _vae_resnet_sp(p["mid_block"]["resnets"][0], x, n, axis, groups)
    x = _vae_attention_sp(p["mid_block"]["attentions"][0], x, n, axis, groups)
    x = _vae_resnet_sp(p["mid_block"]["resnets"][1], x, n, axis, groups)
    for up in p["up_blocks"]:
        for rp in up["resnets"]:
            x = _vae_resnet_sp(rp, x, n, axis, groups)
        if "upsamplers" in up:
            x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)  # local rows
            x = _conv_sp(up["upsamplers"][0]["conv"], x, n, axis)
    x = silu(_group_norm_sp(p["conv_norm_out"], x, n, axis, groups=groups, eps=1e-6))
    return _conv_sp(p["conv_out"], x, n, axis)


def _downsample_sp(p, x, n, axis):
    """diffusers' VAE downsample — pad (0,1,0,1) then 3x3 stride-2 VALID —
    on row-sharded input.  The 3-row window of the last local output row
    reaches one row past the shard, so the halo is one-sided: one fresh row
    from the NEXT device (the last device gets the zero bottom-pad).  Local
    rows are even (pow-2 shard counts on pow-2 sizes), so output windows
    never straddle two shards beyond that single row."""
    if n == 1:
        x = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)))
    else:
        _, from_next = halo_exchange(x, 1, n, axis)  # next device's top row
        x = jnp.pad(
            jnp.concatenate([x, from_next], axis=1), ((0, 0), (0, 0), (0, 1), (0, 0))
        )
    # width pad is materialized above, height rows carry the halo: a VALID
    # stride-2 conv (shared helper, ops/conv.py)
    return _conv_valid_h(p["conv"], x, 2, 0)


def encode_sp(params, cfg: VAEConfig, images, n: int, axis: str = SP_AXIS,
              *, rng=None):
    """Sequence-parallel encode: this device's image row shard
    [B, H/n, W, 3] -> latent row shard [B, H/8n, W/8, 4].  The mean path
    (rng=None) is exact like decode_sp; with ``rng`` each shard samples from
    a per-device fold of the key (statistically equivalent to, but not the
    same draw as, the dense encode).  Rows must stay divisible by 2 per
    downsample (H % 8n == 0)."""
    p = params["encoder"]
    groups = cfg.norm_num_groups
    n_down = sum(1 for d in p["down_blocks"] if "downsamplers" in d)
    assert images.shape[1] % (1 << n_down) == 0, (
        f"local rows {images.shape[1]} not divisible by 2^{n_down} "
        f"(need image height % {n << n_down} == 0 for {n}-way sp encode)"
    )
    if rng is not None and n > 1:
        rng = jax.random.fold_in(rng, lax.axis_index(axis))
    images = images.astype(p["conv_in"]["kernel"].dtype)
    x = _conv_sp(p["conv_in"], images, n, axis)
    for down in p["down_blocks"]:
        for rp in down["resnets"]:
            x = _vae_resnet_sp(rp, x, n, axis, groups)
        if "downsamplers" in down:
            x = _downsample_sp(down["downsamplers"][0], x, n, axis)
    x = _vae_resnet_sp(p["mid_block"]["resnets"][0], x, n, axis, groups)
    x = _vae_attention_sp(p["mid_block"]["attentions"][0], x, n, axis, groups)
    x = _vae_resnet_sp(p["mid_block"]["resnets"][1], x, n, axis, groups)
    x = silu(_group_norm_sp(p["conv_norm_out"], x, n, axis, groups=groups, eps=1e-6))
    x = _conv_sp(p["conv_out"], x, n, axis)  # [B, h/n, w, 8]
    moments = conv2d(params["quant_conv"], x)  # 1x1: local
    mean, logvar = jnp.split(moments, 2, axis=-1)
    if rng is None:
        return mean
    std = jnp.exp(0.5 * jnp.clip(logvar, -30.0, 20.0))
    return mean + std * jax.random.normal(rng, mean.shape, mean.dtype)


def encode(params, cfg: VAEConfig, images, *, rng=None):
    """Image [B, H, W, 3] in [-1,1] -> latent sample [B, H/8, W/8, 4]
    (multiply by scaling_factor for the diffusion space).  Dense path ==
    encode_sp at n == 1, one encoder topology for both modes."""
    return encode_sp(params, cfg, images, 1, rng=rng)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_conv(key, kh, kw, cin, cout):
    return {
        "kernel": jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
        / (cin * kh * kw) ** 0.5,
        "bias": jnp.zeros((cout,), jnp.float32),
    }


def _init_norm(c):
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def _init_vae_resnet(key, cin, cout):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "norm1": _init_norm(cin),
        "conv1": _init_conv(k1, 3, 3, cin, cout),
        "norm2": _init_norm(cout),
        "conv2": _init_conv(k2, 3, 3, cout, cout),
    }
    if cin != cout:
        p["conv_shortcut"] = _init_conv(k3, 1, 1, cin, cout)
    return p


def _init_vae_attn(key, c):
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def lin(k, cin, cout):
        return {
            "kernel": jax.random.normal(k, (cin, cout), jnp.float32) / cin**0.5,
            "bias": jnp.zeros((cout,), jnp.float32),
        }

    return {
        "group_norm": _init_norm(c),
        "to_q": lin(k1, c, c),
        "to_k": lin(k2, c, c),
        "to_v": lin(k3, c, c),
        "to_out": lin(k4, c, c),
    }


def init_vae_params(key, cfg: VAEConfig, dtype=jnp.float32):
    keys = iter(jax.random.split(key, 128))
    nxt = lambda: next(keys)  # noqa: E731
    chs = cfg.block_out_channels
    top = chs[-1]

    def mid(c):
        return {
            "resnets": [_init_vae_resnet(nxt(), c, c), _init_vae_resnet(nxt(), c, c)],
            "attentions": [_init_vae_attn(nxt(), c)],
        }

    # encoder: chs ascending with downsample between
    down_blocks = []
    c_prev = chs[0]
    for i, c in enumerate(chs):
        block = {
            "resnets": [
                _init_vae_resnet(nxt(), c_prev if j == 0 else c, c)
                for j in range(cfg.layers_per_block)
            ]
        }
        if i < len(chs) - 1:
            block["downsamplers"] = [{"conv": _init_conv(nxt(), 3, 3, c, c)}]
        down_blocks.append(block)
        c_prev = c
    encoder = {
        "conv_in": _init_conv(nxt(), 3, 3, cfg.in_channels, chs[0]),
        "down_blocks": down_blocks,
        "mid_block": mid(top),
        "conv_norm_out": _init_norm(top),
        "conv_out": _init_conv(nxt(), 3, 3, top, 2 * cfg.latent_channels),
    }

    # decoder: reversed channels, layers_per_block+1 resnets per block
    rev = list(reversed(chs))
    up_blocks = []
    c_prev = rev[0]
    for i, c in enumerate(rev):
        block = {
            "resnets": [
                _init_vae_resnet(nxt(), c_prev if j == 0 else c, c)
                for j in range(cfg.layers_per_block + 1)
            ]
        }
        if i < len(rev) - 1:
            block["upsamplers"] = [{"conv": _init_conv(nxt(), 3, 3, c, c)}]
        up_blocks.append(block)
        c_prev = c
    decoder = {
        "conv_in": _init_conv(nxt(), 3, 3, cfg.latent_channels, top),
        "mid_block": mid(top),
        "up_blocks": up_blocks,
        "conv_norm_out": _init_norm(rev[-1]),
        "conv_out": _init_conv(nxt(), 3, 3, rev[-1], cfg.out_channels),
    }

    params = {
        "encoder": encoder,
        "decoder": decoder,
        "quant_conv": _init_conv(nxt(), 1, 1, 2 * cfg.latent_channels, 2 * cfg.latent_channels),
        "post_quant_conv": _init_conv(nxt(), 1, 1, cfg.latent_channels, cfg.latent_channels),
    }
    return jax.tree.map(lambda a: a.astype(dtype), params)
