"""Functional UNet2DConditionModel (SDXL / SD 1.x-2.x) in JAX.

This is the one component the reference does NOT reimplement — it monkey-
patches HuggingFace diffusers' torch `UNet2DConditionModel` in place
(/root/reference/distrifuser/models/distri_sdxl_unet_pp.py:18-41).  A TPU
build needs every layer parallelism-aware, so the whole UNet is written here
as a pure function over a param pytree, with all compute routed through a
small *dispatch* object:

* `DenseDispatch`   — single-device ops (the unwrapped diffusers behavior);
* `PatchDispatch`   — displaced patch parallelism: conv_in slices the full
  input to this device's rows (pp/conv2d.py:20-41), k>1 convs exchange halos,
  GroupNorm reduces moments, self-attention gathers KV, cross-attention uses
  pre-computed text KV (pp/attn.py, pp/groupnorm.py semantics);
* `TPDispatch` (models/unet_tp.py) — tensor parallelism.

One UNet definition therefore serves all parallelism modes — the functional
analog of the reference's module surgery, with no mutation and no surgery.

Architecture parity targets diffusers==0.24.0 (the reference's pin,
setup.py:15): ResnetBlock2D, Transformer2DModel + BasicTransformerBlock
(GEGLU FF), Down/Up/Mid blocks, text_time additional embeddings for SDXL.
Param names mirror the diffusers state_dict (see models/weights.py) so the
HF->JAX weight converter is a mechanical transpose.

Activations are NHWC (TPU-native conv layout); attention operates on
[B, H*W, C] tokens where the row-sharded patch is a contiguous token range.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import attention, cross_attention, patch_self_attention
from ..ops.conv import conv2d, patch_conv2d, sliced_conv2d
from ..ops.linear import feed_forward, linear
from ..ops.normalization import group_norm, patch_group_norm
from ..parallel.context import PatchContext

silu = jax.nn.silu


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    """Static architecture description (mirrors the diffusers UNet config)."""

    in_channels: int = 4
    out_channels: int = 4
    block_out_channels: Tuple[int, ...] = (320, 640, 1280)
    down_block_types: Tuple[str, ...] = (
        "DownBlock2D",
        "CrossAttnDownBlock2D",
        "CrossAttnDownBlock2D",
    )
    up_block_types: Tuple[str, ...] = (
        "CrossAttnUpBlock2D",
        "CrossAttnUpBlock2D",
        "UpBlock2D",
    )
    layers_per_block: int = 2
    transformer_layers_per_block: Tuple[int, ...] = (1, 2, 10)
    num_attention_heads: Tuple[int, ...] = (5, 10, 20)
    cross_attention_dim: int = 2048
    norm_num_groups: int = 32
    use_linear_projection: bool = True
    addition_embed_type: Optional[str] = "text_time"  # SDXL; None for SD 1.x
    addition_time_embed_dim: int = 256
    projection_class_embeddings_input_dim: int = 2816
    flip_sin_to_cos: bool = True
    freq_shift: int = 0

    @property
    def time_embed_dim(self) -> int:
        return self.block_out_channels[0] * 4

    def heads_for_block(self, i: int) -> int:
        return self.num_attention_heads[i]


def sdxl_config() -> UNetConfig:
    """SDXL-base UNet (stabilityai/stable-diffusion-xl-base-1.0)."""
    return UNetConfig()


def sd15_config() -> UNetConfig:
    """SD 1.4/1.5 UNet (runwayml/stable-diffusion-v1-5 and compatible).

    The reference's `DistriSDPipeline` targets these (pipelines.py:170-299).
    """
    return UNetConfig(
        block_out_channels=(320, 640, 1280, 1280),
        down_block_types=(
            "CrossAttnDownBlock2D",
            "CrossAttnDownBlock2D",
            "CrossAttnDownBlock2D",
            "DownBlock2D",
        ),
        up_block_types=(
            "UpBlock2D",
            "CrossAttnUpBlock2D",
            "CrossAttnUpBlock2D",
            "CrossAttnUpBlock2D",
        ),
        transformer_layers_per_block=(1, 1, 1, 1),
        num_attention_heads=(8, 8, 8, 8),
        cross_attention_dim=768,
        use_linear_projection=False,
        addition_embed_type=None,
        projection_class_embeddings_input_dim=0,
    )


def sd21_config() -> UNetConfig:
    """SD 2.0/2.1 UNet (stabilityai/stable-diffusion-2-1 and compatible):
    SD1.x block structure, OpenCLIP ViT-H conditioning (1024), uniform
    64-dim heads, linear transformer projections."""
    return UNetConfig(
        block_out_channels=(320, 640, 1280, 1280),
        down_block_types=(
            "CrossAttnDownBlock2D",
            "CrossAttnDownBlock2D",
            "CrossAttnDownBlock2D",
            "DownBlock2D",
        ),
        up_block_types=(
            "UpBlock2D",
            "CrossAttnUpBlock2D",
            "CrossAttnUpBlock2D",
            "CrossAttnUpBlock2D",
        ),
        transformer_layers_per_block=(1, 1, 1, 1),
        num_attention_heads=(5, 10, 20, 20),
        cross_attention_dim=1024,
        use_linear_projection=True,
        addition_embed_type=None,
        projection_class_embeddings_input_dim=0,
    )


_SUPPORTED_DOWN_BLOCKS = {"DownBlock2D", "CrossAttnDownBlock2D"}
_SUPPORTED_UP_BLOCKS = {"UpBlock2D", "CrossAttnUpBlock2D"}


def load_config_source(source) -> Dict[str, Any]:
    """Normalize a config source: a json file path (str/PathLike) or an
    already-parsed mapping.  Shared by the unet/clip/vae config loaders."""
    import os

    if isinstance(source, (str, bytes, os.PathLike)):
        import json

        with open(source) as f:
            return json.load(f)
    return dict(source)


def unet_config_from_json(source) -> UNetConfig:
    """Build a UNetConfig from a diffusers `unet/config.json` (path or dict).

    The reference never needs this — it calls diffusers `from_pretrained`,
    which instantiates the architecture from this very file
    (/root/reference/distrifuser/pipelines.py:30-42).  Reading it here makes
    every SD-family snapshot (1.4/1.5, 2.0/2.1 base+v, SDXL-base — and
    refiner-architecture UNets via from_params; the refiner's img2img
    *pipeline* is out of scope here, as in the reference) load with its true
    architecture instead of a hardcoded preset.

    Notes on diffusers quirks reproduced here:
    * `attention_head_dim` in these configs historically means *number of
      heads* per block when `num_attention_heads` is absent (SD1.5's 8,
      SD2.1's [5,10,20,20], SDXL's [5,10,20]) — diffusers carries the same
      naming bug forward for backwards compatibility.
    * scalar fields broadcast over blocks (`transformer_layers_per_block: 1`).
    * flag fields appear as scalars or per-block lists; a list of falses
      (diffusers' re-saved form) means disabled, same as `false`.
    """
    cfg = load_config_source(source)

    def per_block(value, default):
        v = cfg.get(value, default)
        if isinstance(v, (list, tuple)):
            return tuple(v)
        return (v,) * len(blocks)

    blocks = tuple(cfg["block_out_channels"])
    down = tuple(cfg["down_block_types"])
    up = tuple(cfg["up_block_types"])
    unsupported = (set(down) - _SUPPORTED_DOWN_BLOCKS) | (
        set(up) - _SUPPORTED_UP_BLOCKS
    )
    def enabled(v):
        # scalar-or-per-block-list flag; [false, false, ...] means disabled
        return any(v) if isinstance(v, (list, tuple)) else bool(v)

    # key-present-with-null is valid diffusers and means "no mid block" —
    # unsupported here just like any nonstandard type
    mid = cfg["mid_block_type"] if "mid_block_type" in cfg else "UNetMidBlock2DCrossAttn"
    mid_bad = "null (no mid block)" if mid is None else mid
    for key, bad in (
        ("block types", unsupported),
        ("class_embed_type", cfg.get("class_embed_type")),
        ("encoder_hid_dim", cfg.get("encoder_hid_dim")),
        ("dual_cross_attention", enabled(cfg.get("dual_cross_attention"))),
        ("only_cross_attention", enabled(cfg.get("only_cross_attention"))),
        # LCM-distilled guidance embedding: weights would be silently dropped
        ("time_cond_proj_dim", cfg.get("time_cond_proj_dim")),
        ("class_embeddings_concat", cfg.get("class_embeddings_concat")),
        ("mid_block_type", None if mid == "UNetMidBlock2DCrossAttn" else mid_bad),
    ):
        if bad:
            raise NotImplementedError(
                f"unsupported UNet config: {key}={bad!r} (supported: the "
                "SD1.x/SD2.x/SDXL UNet2DConditionModel family)"
            )
    add_type = cfg.get("addition_embed_type")
    if add_type not in (None, "text_time"):
        raise NotImplementedError(
            f"unsupported addition_embed_type {add_type!r}"
        )
    # diffusers defaults attention_head_dim=8 (meaning 8 heads, see above)
    heads = cfg.get("num_attention_heads") or cfg.get("attention_head_dim", 8)
    if not isinstance(heads, (list, tuple)):
        heads = (heads,) * len(blocks)
    cross = cfg.get("cross_attention_dim", 1280)
    if isinstance(cross, (list, tuple)):
        uniq = set(cross)
        if len(uniq) != 1:
            raise NotImplementedError(
                f"per-block cross_attention_dim {cross!r} unsupported"
            )
        cross = cross[0]
    return UNetConfig(
        in_channels=cfg.get("in_channels", 4),
        out_channels=cfg.get("out_channels", 4),
        block_out_channels=blocks,
        down_block_types=down,
        up_block_types=up,
        layers_per_block=cfg.get("layers_per_block", 2),
        transformer_layers_per_block=per_block("transformer_layers_per_block", 1),
        num_attention_heads=tuple(heads),
        cross_attention_dim=cross,
        norm_num_groups=cfg.get("norm_num_groups", 32),
        use_linear_projection=cfg.get("use_linear_projection", False),
        addition_embed_type=add_type,
        addition_time_embed_dim=cfg.get("addition_time_embed_dim", 256) or 256,
        projection_class_embeddings_input_dim=cfg.get(
            "projection_class_embeddings_input_dim", 0
        )
        or 0,
        flip_sin_to_cos=cfg.get("flip_sin_to_cos", True),
        freq_shift=cfg.get("freq_shift", 0),
    )


def tiny_config(cross_attention_dim: int = 32, sdxl: bool = False) -> UNetConfig:
    """Small UNet with the full SDXL block structure, for tests."""
    return UNetConfig(
        block_out_channels=(32, 64),
        down_block_types=("DownBlock2D", "CrossAttnDownBlock2D"),
        up_block_types=("CrossAttnUpBlock2D", "UpBlock2D"),
        layers_per_block=1,
        transformer_layers_per_block=(1, 1),
        num_attention_heads=(2, 4),
        cross_attention_dim=cross_attention_dim,
        norm_num_groups=8,
        use_linear_projection=True,
        addition_embed_type="text_time" if sdxl else None,
        addition_time_embed_dim=8,
        projection_class_embeddings_input_dim=32 + 8 * 6 if sdxl else 0,
    )


# ---------------------------------------------------------------------------
# Dispatch: how each primitive executes under a given parallelism
# ---------------------------------------------------------------------------


class DenseDispatch:
    """Single-device execution (diffusers-equivalent)."""

    def __init__(self, text_kv: Optional[Dict[str, Any]] = None):
        self.text_kv = text_kv or {}

    def conv_in(self, p, x, name):
        return conv2d(p, x)

    def conv(self, p, x, name, *, stride=1):
        return conv2d(p, x, stride=stride)

    def group_norm(self, p, x, name, *, groups, eps=1e-5):
        return group_norm(p, x, groups=groups, eps=eps)

    def self_attn(self, p, x, name, *, heads):
        return attention(p, x, heads=heads)

    def cross_attn(self, p, x, name, *, heads, enc):
        return cross_attention(
            p, x, heads=heads, encoder_hidden_states=enc,
            cached_kv=self.text_kv.get(name),
        )

    def feed_forward(self, p, x, name):
        return feed_forward(p, x)

    def resnet(self, p, x, temb, name, *, groups):
        return resnet_block(self, p, x, temb, name, groups=groups)


class PatchDispatch:
    """Displaced patch parallelism over the sp mesh axis (must run in shard_map)."""

    def __init__(self, ctx: PatchContext):
        self.ctx = ctx

    def conv_in(self, p, x, name):
        # first layer: full input, compute only this device's rows
        return sliced_conv2d(p, x, self.ctx)

    def conv(self, p, x, name, *, stride=1):
        return patch_conv2d(p, x, self.ctx, name, stride=stride)

    def group_norm(self, p, x, name, *, groups, eps=1e-5):
        return patch_group_norm(p, x, self.ctx, name, groups=groups, eps=eps)

    def self_attn(self, p, x, name, *, heads):
        if self.ctx.attn_impl == "ring":
            from ..ops.ring_attention import ring_self_attention

            return ring_self_attention(p, x, self.ctx, name, heads=heads)
        return patch_self_attention(p, x, self.ctx, name, heads=heads)

    def cross_attn(self, p, x, name, *, heads, enc):
        cached = None if self.ctx.text_kv is None else self.ctx.text_kv.get(name)
        return cross_attention(
            p, x, heads=heads, encoder_hidden_states=enc, cached_kv=cached
        )

    def feed_forward(self, p, x, name):
        return feed_forward(p, x)  # purely local over tokens

    def resnet(self, p, x, temb, name, *, groups):
        return resnet_block(self, p, x, temb, name, groups=groups)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def timestep_embedding(
    t, dim: int, *, flip_sin_to_cos: bool = True, freq_shift: int = 0,
    max_period: int = 10000,
):
    """diffusers get_timestep_embedding parity (models/embeddings.py there)."""
    half = dim // 2
    exponent = -math.log(max_period) * jnp.arange(half, dtype=jnp.float32)
    exponent = exponent / (half - freq_shift)
    emb = t.astype(jnp.float32)[:, None] * jnp.exp(exponent)[None, :]
    emb = jnp.concatenate([jnp.sin(emb), jnp.cos(emb)], axis=-1)
    if flip_sin_to_cos:
        emb = jnp.concatenate([emb[:, half:], emb[:, :half]], axis=-1)
    return emb


@jax.named_scope("layernorm")
def layer_norm(p, x, eps: float = 1e-5):
    """Moments in fp32 (torch upcasts low-precision LN internally; bf16's
    8-bit mantissa cannot accumulate a 1280-wide mean), output in x.dtype."""
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=-1, keepdims=True)
    var = jnp.square(x32 - mean).mean(axis=-1, keepdims=True)
    y = ((x32 - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return y * p["scale"] + p["bias"]


def resnet_block(d, p, x, temb, name, *, groups: int):
    """diffusers ResnetBlock2D (the TP shard of it is tp/resnet.py:117-202)."""
    h = d.group_norm(p["norm1"], x, f"{name}.norm1", groups=groups)
    h = d.conv(p["conv1"], silu(h), f"{name}.conv1")
    t = linear(p["time_emb_proj"], silu(temb))
    h = h + t[:, None, None, :]
    h = d.group_norm(p["norm2"], h, f"{name}.norm2", groups=groups)
    h = d.conv(p["conv2"], silu(h), f"{name}.conv2")
    if "conv_shortcut" in p:
        x = conv2d(p["conv_shortcut"], x)  # 1x1: local everywhere
    return x + h


def basic_transformer_block(d, p, x, enc, name, *, heads: int):
    """diffusers BasicTransformerBlock: self-attn, cross-attn, GEGLU FF."""
    x = x + d.self_attn(p["attn1"], layer_norm(p["norm1"], x), f"{name}.attn1", heads=heads)
    x = x + d.cross_attn(p["attn2"], layer_norm(p["norm2"], x), f"{name}.attn2", heads=heads, enc=enc)
    x = x + d.feed_forward(p["ff"], layer_norm(p["norm3"], x), f"{name}.ff")
    return x


def transformer_2d(d, p, x, enc, name, *, heads: int, use_linear_projection: bool,
                   norm_groups: int = 32):
    b, h, w, c = x.shape
    residual = x
    hs = d.group_norm(p["norm"], x, f"{name}.norm", groups=norm_groups, eps=1e-6)
    if use_linear_projection:
        hs = hs.reshape(b, h * w, c)
        hs = linear(p["proj_in"], hs)
    else:
        hs = conv2d(p["proj_in"], hs)  # 1x1 conv
        hs = hs.reshape(b, h * w, c)
    for i, bp in enumerate(p["transformer_blocks"]):
        hs = basic_transformer_block(d, bp, hs, enc, f"{name}.transformer_blocks.{i}", heads=heads)
    if use_linear_projection:
        hs = linear(p["proj_out"], hs)
        hs = hs.reshape(b, h, w, c)
    else:
        hs = hs.reshape(b, h, w, c)
        hs = conv2d(p["proj_out"], hs)
    return hs + residual


def upsample_nearest_2x(x):
    x = jnp.repeat(x, 2, axis=1)
    return jnp.repeat(x, 2, axis=2)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def unet_forward(
    params,
    cfg: UNetConfig,
    sample,
    timesteps,
    encoder_hidden_states,
    *,
    dispatch=None,
    added_cond: Optional[Dict[str, Any]] = None,
    cache_depth: int = 0,
    deep_cache=None,
):
    """Full UNet forward.

    ``sample``: [B, H, W, C] latent — the *full* latent in patch mode (conv_in
    slices to local rows, matching the reference where every rank receives the
    full input, distri_sdxl_unet_pp.py:134-146).  Returns [B, h(_local), W, C].

    Temporal step-cache entry points (parallel/stepcache.py): with
    ``cache_depth = K > 0`` the deepest K resolution levels — down blocks
    ``L-K..L-1``, the mid block, and up blocks ``0..K-1`` — form the *deep
    subtree*, and the return value becomes ``(out, deep)``:

    * ``deep_cache is None`` (a **full** step): everything runs; ``deep`` is
      the freshly computed deep-subtree output — the feature entering up
      block K, after up block K-1's upsampler — for the carry;
    * ``deep_cache`` given (a **shallow** step): only the shallow layers
      execute — down blocks ``0..L-K-1`` minus block ``L-K-1``'s downsampler
      (it feeds the deep subtree only), then up blocks ``K..L-1`` resuming
      from ``deep_cache``; ``deep`` returns None (the caller keeps carrying
      its cache).  Skip-connection bookkeeping is exact: the shallow layers
      push precisely the skips the shallow up blocks pop.
    """
    d = dispatch or DenseDispatch()
    n_levels = len(cfg.block_out_channels)
    if cache_depth and not 1 <= cache_depth < n_levels:
        raise ValueError(
            f"cache_depth must be in [1, {n_levels - 1}] for "
            f"{n_levels}-level UNet, got {cache_depth}"
        )
    cut = n_levels - cache_depth  # first deep down-block index
    shallow = cache_depth > 0 and deep_cache is not None
    dtype = params["conv_in"]["kernel"].dtype
    b = sample.shape[0]
    if jnp.ndim(timesteps) == 0:
        timesteps = jnp.full((b,), timesteps)

    # --- time + additional embeddings ---
    with jax.named_scope("time_embed"):
        temb = timestep_embedding(
            timesteps, cfg.block_out_channels[0],
            flip_sin_to_cos=cfg.flip_sin_to_cos, freq_shift=cfg.freq_shift,
        ).astype(dtype)
        temb = linear(params["time_embedding"]["linear_2"],
                      silu(linear(params["time_embedding"]["linear_1"], temb)))
        if cfg.addition_embed_type == "text_time":
            assert added_cond is not None, "SDXL needs added_cond text_embeds/time_ids"
            time_ids = added_cond["time_ids"]  # [B, n_ids] (6 base / 5 refiner)
            tid_emb = timestep_embedding(
                time_ids.reshape(-1), cfg.addition_time_embed_dim,
                flip_sin_to_cos=cfg.flip_sin_to_cos, freq_shift=cfg.freq_shift,
            ).reshape(b, -1).astype(dtype)
            add = jnp.concatenate([added_cond["text_embeds"].astype(dtype), tid_emb], axis=-1)
            temb = temb + linear(params["add_embedding"]["linear_2"],
                                 silu(linear(params["add_embedding"]["linear_1"], add)))

    enc = encoder_hidden_states.astype(dtype)
    groups = cfg.norm_num_groups

    # --- down path ---
    x = d.conv_in(params["conv_in"], sample.astype(dtype), "conv_in")
    skips = [x]
    for i, btype in enumerate(cfg.down_block_types):
        if shallow and i >= cut:
            break
        with jax.named_scope(f"down_{i}"):
            bp = params["down_blocks"][i]
            for j in range(cfg.layers_per_block):
                name = f"down_blocks.{i}.resnets.{j}"
                x = d.resnet(bp["resnets"][j], x, temb, name, groups=groups)
                if btype == "CrossAttnDownBlock2D":
                    x = transformer_2d(
                        d, bp["attentions"][j], x, enc, f"down_blocks.{i}.attentions.{j}",
                        heads=cfg.heads_for_block(i),
                        use_linear_projection=cfg.use_linear_projection,
                        norm_groups=groups,
                    )
                skips.append(x)
            if i < len(cfg.down_block_types) - 1 and not (shallow and i == cut - 1):
                # block cut-1's downsampler feeds the deep subtree only
                x = d.conv(bp["downsamplers"][0]["conv"], x,
                           f"down_blocks.{i}.downsamplers.0.conv", stride=2)
                skips.append(x)

    if not shallow:
        # --- mid ---
        with jax.named_scope("mid"):
            mp = params["mid_block"]
            x = d.resnet(mp["resnets"][0], x, temb, "mid_block.resnets.0", groups=groups)
            x = transformer_2d(
                d, mp["attentions"][0], x, enc, "mid_block.attentions.0",
                heads=cfg.heads_for_block(len(cfg.block_out_channels) - 1),
                use_linear_projection=cfg.use_linear_projection, norm_groups=groups,
            )
            x = d.resnet(mp["resnets"][1], x, temb, "mid_block.resnets.1", groups=groups)

    # --- up path ---
    deep_out = None
    n_blocks = len(cfg.block_out_channels)
    for i, btype in enumerate(cfg.up_block_types):
        if shallow and i < cache_depth:
            continue
        if cache_depth and i == cache_depth:
            if shallow:
                x = deep_cache
            else:
                deep_out = x
        with jax.named_scope(f"up_{i}"):
            bp = params["up_blocks"][i]
            for j in range(cfg.layers_per_block + 1):
                skip = skips.pop()
                x = jnp.concatenate([x, skip], axis=-1)
                name = f"up_blocks.{i}.resnets.{j}"
                x = d.resnet(bp["resnets"][j], x, temb, name, groups=groups)
                if btype == "CrossAttnUpBlock2D":
                    x = transformer_2d(
                        d, bp["attentions"][j], x, enc, f"up_blocks.{i}.attentions.{j}",
                        heads=cfg.heads_for_block(n_blocks - 1 - i),
                        use_linear_projection=cfg.use_linear_projection,
                        norm_groups=groups,
                    )
            if i < len(cfg.up_block_types) - 1:
                x = upsample_nearest_2x(x)
                x = d.conv(bp["upsamplers"][0]["conv"], x, f"up_blocks.{i}.upsamplers.0.conv")

    assert not skips
    x = d.group_norm(params["conv_norm_out"], x, "conv_norm_out", groups=groups)
    x = d.conv(params["conv_out"], silu(x), "conv_out")
    if cache_depth:
        return x, deep_out
    return x


def precompute_text_kv(params, encoder_hidden_states):
    """Text-encoder KV for every cross-attention layer, computed once per
    generation (the reference caches at counter==0, pp/attn.py:56,73-77).

    Returns {layer_name: [B, L_text, 2C]} keyed identically to the forward's
    cross-attn names.

    The cache is computed OUTSIDE unet_forward, so it must apply the same
    model-dtype entry cast the forward applies to its own inputs
    (unet_forward casts enc at its top): fp32 prompt embeds would otherwise
    produce fp32 KV whose cross-attention output silently upcasts the whole
    residual stream — at 2x the HBM bytes — for the rest of the UNet.
    """
    out = {}

    def walk(tree, path):
        if isinstance(tree, dict):
            for k, v in tree.items():
                if k == "attn2" and isinstance(v, dict):
                    enc = encoder_hidden_states.astype(v["to_kv"]["kernel"].dtype)
                    out[f"{path}.{k}" if path else k] = linear(v["to_kv"], enc)
                elif isinstance(v, (dict, list)):
                    walk(v, f"{path}.{k}" if path else k)
        elif isinstance(tree, list):
            for i, v in enumerate(tree):
                walk(v, f"{path}.{i}")

    walk(params, "")
    return out


# ---------------------------------------------------------------------------
# Parameter init (random; HF weight loading lives in models/weights.py)
# ---------------------------------------------------------------------------


def _init_linear(key, cin, cout, bias=True, scale=None):
    k1, _ = jax.random.split(key)
    scale = scale if scale is not None else 1.0 / math.sqrt(cin)
    p = {"kernel": jax.random.normal(k1, (cin, cout), jnp.float32) * scale}
    if bias:
        p["bias"] = jnp.zeros((cout,), jnp.float32)
    return p


def _init_conv(key, kh, kw, cin, cout, bias=True):
    k1, _ = jax.random.split(key)
    scale = 1.0 / math.sqrt(cin * kh * kw)
    p = {"kernel": jax.random.normal(k1, (kh, kw, cin, cout), jnp.float32) * scale}
    if bias:
        p["bias"] = jnp.zeros((cout,), jnp.float32)
    return p


def _init_norm(c):
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def _init_attn(key, c, heads, kv_dim=None):
    kv_dim = kv_dim or c
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "to_q": _init_linear(k1, c, c, bias=False),
        "to_kv": _init_linear(k2, kv_dim, 2 * c, bias=False),
        "to_out": _init_linear(k3, c, c, bias=True),
    }


def _init_resnet(key, cin, cout, temb_dim, groups):
    ks = jax.random.split(key, 4)
    p = {
        "norm1": _init_norm(cin),
        "conv1": _init_conv(ks[0], 3, 3, cin, cout),
        "time_emb_proj": _init_linear(ks[1], temb_dim, cout),
        "norm2": _init_norm(cout),
        "conv2": _init_conv(ks[2], 3, 3, cout, cout),
    }
    if cin != cout:
        p["conv_shortcut"] = _init_conv(ks[3], 1, 1, cin, cout)
    return p


def _init_transformer(key, c, heads, n_layers, cross_dim, use_linear):
    ks = jax.random.split(key, n_layers + 2)
    blocks = []
    for i in range(n_layers):
        b1, b2, b3 = jax.random.split(ks[i], 3)
        blocks.append(
            {
                "norm1": _init_norm(c),
                "attn1": _init_attn(b1, c, heads),
                "norm2": _init_norm(c),
                "attn2": _init_attn(b2, c, heads, kv_dim=cross_dim),
                "norm3": _init_norm(c),
                "ff": {
                    "net_0": {"proj": _init_linear(jax.random.fold_in(b3, 0), c, 8 * c)},
                    "net_2": _init_linear(jax.random.fold_in(b3, 1), 4 * c, c),
                },
            }
        )
    proj_init = (
        (lambda k: _init_linear(k, c, c))
        if use_linear
        else (lambda k: _init_conv(k, 1, 1, c, c))
    )
    return {
        "norm": _init_norm(c),
        "proj_in": proj_init(ks[-2]),
        "transformer_blocks": blocks,
        "proj_out": proj_init(ks[-1]),
    }


def init_unet_params(key, cfg: UNetConfig, dtype=jnp.float32):
    """Random-init param pytree with the exact structure the converter fills."""
    keys = iter(jax.random.split(key, 256))
    nxt = lambda: next(keys)  # noqa: E731
    ch0 = cfg.block_out_channels[0]
    temb_dim = cfg.time_embed_dim

    params: Dict[str, Any] = {
        "conv_in": _init_conv(nxt(), 3, 3, cfg.in_channels, ch0),
        "time_embedding": {
            "linear_1": _init_linear(nxt(), ch0, temb_dim),
            "linear_2": _init_linear(nxt(), temb_dim, temb_dim),
        },
    }
    if cfg.addition_embed_type == "text_time":
        params["add_embedding"] = {
            "linear_1": _init_linear(nxt(), cfg.projection_class_embeddings_input_dim, temb_dim),
            "linear_2": _init_linear(nxt(), temb_dim, temb_dim),
        }

    down_blocks = []
    out_ch = ch0
    for i, btype in enumerate(cfg.down_block_types):
        in_ch, out_ch = out_ch, cfg.block_out_channels[i]
        # blocks without cross-attention carry no "attentions" key, matching
        # the state_dict structure the converter produces
        block: Dict[str, Any] = {"resnets": []}
        if btype == "CrossAttnDownBlock2D":
            block["attentions"] = []
        for j in range(cfg.layers_per_block):
            block["resnets"].append(
                _init_resnet(nxt(), in_ch if j == 0 else out_ch, out_ch, temb_dim, cfg.norm_num_groups)
            )
            if btype == "CrossAttnDownBlock2D":
                block["attentions"].append(
                    _init_transformer(
                        nxt(), out_ch, cfg.heads_for_block(i),
                        cfg.transformer_layers_per_block[i],
                        cfg.cross_attention_dim, cfg.use_linear_projection,
                    )
                )
        if i < len(cfg.down_block_types) - 1:
            block["downsamplers"] = [{"conv": _init_conv(nxt(), 3, 3, out_ch, out_ch)}]
        down_blocks.append(block)
    params["down_blocks"] = down_blocks

    mid_ch = cfg.block_out_channels[-1]
    params["mid_block"] = {
        "resnets": [
            _init_resnet(nxt(), mid_ch, mid_ch, temb_dim, cfg.norm_num_groups),
            _init_resnet(nxt(), mid_ch, mid_ch, temb_dim, cfg.norm_num_groups),
        ],
        "attentions": [
            _init_transformer(
                nxt(), mid_ch, cfg.heads_for_block(len(cfg.block_out_channels) - 1),
                cfg.transformer_layers_per_block[-1],
                cfg.cross_attention_dim, cfg.use_linear_projection,
            )
        ],
    }

    up_blocks = []
    rev = list(reversed(cfg.block_out_channels))
    rev_tf = list(reversed(cfg.transformer_layers_per_block))
    prev_out = rev[0]
    for i, btype in enumerate(cfg.up_block_types):
        out_ch = rev[i]
        in_ch = rev[min(i + 1, len(rev) - 1)]
        block = {"resnets": []}
        if btype == "CrossAttnUpBlock2D":
            block["attentions"] = []
        for j in range(cfg.layers_per_block + 1):
            skip_ch = in_ch if j == cfg.layers_per_block else out_ch
            res_in = prev_out if j == 0 else out_ch
            block["resnets"].append(
                _init_resnet(nxt(), res_in + skip_ch, out_ch, temb_dim, cfg.norm_num_groups)
            )
            if btype == "CrossAttnUpBlock2D":
                block["attentions"].append(
                    _init_transformer(
                        nxt(), out_ch, cfg.heads_for_block(len(rev) - 1 - i),
                        rev_tf[i], cfg.cross_attention_dim, cfg.use_linear_projection,
                    )
                )
        if i < len(cfg.up_block_types) - 1:
            block["upsamplers"] = [{"conv": _init_conv(nxt(), 3, 3, out_ch, out_ch)}]
        prev_out = out_ch
        up_blocks.append(block)
    params["up_blocks"] = up_blocks

    params["conv_norm_out"] = _init_norm(cfg.block_out_channels[0])
    params["conv_out"] = _init_conv(nxt(), 3, 3, cfg.block_out_channels[0], cfg.out_channels)
    return jax.tree.map(lambda a: a.astype(dtype), params)
