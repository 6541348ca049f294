"""Functional Diffusion-Transformer (DiT / PixArt-style) in JAX.

The reference framework (mit-han-lab/distrifuser) targets the SD/SDXL UNet
only; its successor line of work (PipeFusion, arXiv 2405.14430 — PAPERS.md)
applies patch-level *pipeline* parallelism to diffusion transformers, where
the uniform block stack makes layer pipelining natural.  This module is the
model side of that extension: a PixArt-alpha-style DiT (arXiv 2310.00426
block structure: adaLN-single conditioning, self-attn -> cross-attn -> MLP)
written the TPU way —

* every block has identical shapes, so the whole stack is ONE stacked param
  pytree with a leading ``depth`` axis, consumed by `lax.scan` (dense path)
  or sharded over the ``sp`` mesh axis as pipeline stages
  (parallel/pipefusion.py);
* activations are token-major ``[B, N, hidden]``; patchify/unpatchify are
  reshapes + one linear, so a "patch" of the image is a contiguous token
  range — the same contract the displaced-patch UNet uses for row shards;
* the attention core is ops.attention.sdpa (Pallas flash on TPU, chunked XLA
  fallback elsewhere); K/V projections are fused into one matmul.

The block math (t2i modulation) follows the PixArt-alpha paper: with
``(s1, sc1, g1, s2, sc2, g2) = table + adaln(t)`` per block,

    x = x + g1 * attn(ln(x) * (1 + sc1) + s1)
    x = x + cross_attn(x, text)
    x = x + g2 * mlp(ln(x) * (1 + sc2) + s2)

and the final layer applies ``ln(x) * (1 + sc) + s`` from a 2-entry table
before the linear projection to patch pixels.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import sdpa
from ..ops.linear import linear

silu = jax.nn.silu


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DiTConfig:
    """Static architecture description (PixArt-alpha-style DiT)."""

    sample_size: int = 128          # latent H = W (1024 px / 8)
    patch_size: int = 2
    in_channels: int = 4
    out_channels: int = 4           # epsilon only (learned-sigma heads unused)
    hidden_size: int = 1152
    depth: int = 28
    num_heads: int = 16
    mlp_ratio: int = 4
    caption_dim: int = 4096         # text-encoder hidden size fed to cross-attn
    frequency_embedding_size: int = 256
    # PixArt 1024-class checkpoints micro-condition on (resolution, aspect
    # ratio); the embedders live in the param tree and fold_size_condition
    # applies them (exactly) ahead of the denoise loop
    use_additional_conditions: bool = False
    # Positional-embedding coordinate scaling (diffusers PatchEmbed):
    # coords = arange(side) / (side / base_size) / interpolation_scale.
    # PixArt trains 1024-class models with interpolation_scale=2 over a
    # base grid of 64 — raw arange coords would put every token's embedding
    # at 2x the trained frequency.  base_size None = tokens_per_side.
    interpolation_scale: float = 1.0
    pos_embed_base_size: Optional[int] = None

    @property
    def tokens_per_side(self) -> int:
        return self.sample_size // self.patch_size

    @property
    def num_tokens(self) -> int:
        return self.tokens_per_side ** 2

    @property
    def token_dim(self) -> int:
        """Pixels carried by one token of the patchified latent."""
        return self.patch_size * self.patch_size * self.in_channels

    @property
    def token_out_dim(self) -> int:
        return self.patch_size * self.patch_size * self.out_channels

    def __post_init__(self):
        if self.sample_size % self.patch_size != 0:
            raise ValueError("sample_size must be divisible by patch_size")
        if self.hidden_size % self.num_heads != 0:
            raise ValueError("hidden_size must be divisible by num_heads")


def pixart_config(sample_size: int = 128) -> DiTConfig:
    """PixArt-alpha-XL/2 geometry: T5-v1.1-XXL caption width (models/t5.py
    is the matching in-repo encoder); 1024-class checkpoints (latent side
    128) additionally micro-condition on resolution/aspect and train with
    interpolation_scale=2 positional coordinates."""
    return DiTConfig(
        sample_size=sample_size,
        use_additional_conditions=sample_size == 128,
        interpolation_scale=float(max(sample_size // 64, 1)),
        pos_embed_base_size=sample_size // 2,
    )


def dit_config_from_json(source) -> DiTConfig:
    """diffusers PixArtTransformer2DModel config.json -> DiTConfig.

    ``out_channels`` collapses to ``in_channels``: diffusers' 2x head is
    (epsilon, learned sigma) and the learned-sigma rows are dropped at
    conversion (weights.convert_pixart_state_dict), since the runners use
    fixed variance like the reference's SDXL path."""
    if isinstance(source, (str, os.PathLike)):
        with open(source) as f:
            source = json.load(f)
    d = dict(source)
    heads = d.get("num_attention_heads", 16)
    sample = d.get("sample_size", 128)
    ps = d.get("patch_size", 2)
    return DiTConfig(
        sample_size=sample,
        patch_size=ps,
        in_channels=d.get("in_channels", 4),
        out_channels=d.get("in_channels", 4),
        hidden_size=heads * d.get("attention_head_dim", 72),
        depth=d.get("num_layers", 28),
        num_heads=heads,
        mlp_ratio=4,
        caption_dim=d.get("caption_channels", 4096),
        use_additional_conditions=d.get(
            "use_additional_conditions", sample == 128
        ),
        # diffusers: config value, else max(sample_size // 64, 1)
        interpolation_scale=float(
            d.get("interpolation_scale") or max(sample // 64, 1)
        ),
        pos_embed_base_size=sample // ps,
    )


def tiny_dit_config(depth: int = 8) -> DiTConfig:
    """Small config for tests: real structure, toy widths."""
    return DiTConfig(
        sample_size=16,
        patch_size=2,
        hidden_size=64,
        depth=depth,
        num_heads=4,
        mlp_ratio=2,
        caption_dim=32,
    )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_linear(key, d_in, d_out, dtype):
    k1, _ = jax.random.split(key)
    scale = 1.0 / math.sqrt(d_in)
    return {
        "kernel": jax.random.uniform(k1, (d_in, d_out), dtype, -scale, scale),
        "bias": jnp.zeros((d_out,), dtype),
    }


def _init_block(key, cfg: DiTConfig, dtype):
    h = cfg.hidden_size
    keys = jax.random.split(key, 8)
    return {
        "scale_shift_table": jax.random.normal(keys[0], (6, h), dtype) / h**0.5,
        "attn_q": _init_linear(keys[1], h, h, dtype),
        "attn_kv": _init_linear(keys[2], h, 2 * h, dtype),
        "attn_out": _init_linear(keys[3], h, h, dtype),
        "cross_q": _init_linear(keys[4], h, h, dtype),
        "cross_kv": _init_linear(keys[5], h, 2 * h, dtype),
        "cross_out": _init_linear(keys[6], h, h, dtype),
        "mlp_fc1": _init_linear(keys[7], h, cfg.mlp_ratio * h, dtype),
        "mlp_fc2": _init_linear(jax.random.fold_in(key, 99), cfg.mlp_ratio * h, h, dtype),
    }


def init_dit_params(key, cfg: DiTConfig, dtype=jnp.float32) -> Dict[str, Any]:
    """Random-init parameter pytree.

    ``blocks`` leaves carry a leading ``[depth]`` axis (stacked uniform
    blocks) — the layout `lax.scan` consumes directly and the pipefusion
    runner shards over the ``sp`` axis.
    """
    h = cfg.hidden_size
    keys = jax.random.split(key, 8)
    block_keys = jax.random.split(keys[7], cfg.depth)
    blocks = jax.vmap(lambda k: _init_block(k, cfg, dtype))(block_keys)
    extra = {}
    if cfg.use_additional_conditions:
        if h % 3 != 0:
            raise ValueError(
                "use_additional_conditions needs hidden_size % 3 == 0 "
                "(resolution h+w and aspect embeddings concatenate to hidden)"
            )
        for i, name in enumerate(("resolution_embedder", "aspect_ratio_embedder")):
            k = jax.random.fold_in(keys[6], 10 + i)
            extra[name] = {
                "fc1": _init_linear(k, cfg.frequency_embedding_size, h // 3, dtype),
                "fc2": _init_linear(jax.random.fold_in(k, 1), h // 3, h // 3, dtype),
            }
    return {
        **extra,
        "proj_in": _init_linear(keys[0], cfg.token_dim, h, dtype),
        "t_fc1": _init_linear(keys[1], cfg.frequency_embedding_size, h, dtype),
        "t_fc2": _init_linear(keys[2], h, h, dtype),
        "adaln": _init_linear(keys[3], h, 6 * h, dtype),
        "cap_fc1": _init_linear(keys[4], cfg.caption_dim, h, dtype),
        "cap_fc2": _init_linear(keys[5], h, h, dtype),
        "final_table": jax.random.normal(keys[6], (2, h), dtype) / h**0.5,
        "final_out": _init_linear(jax.random.fold_in(keys[6], 1), h,
                                  cfg.token_out_dim, dtype),
        "blocks": blocks,
    }


# ---------------------------------------------------------------------------
# Pieces shared by the dense forward and the pipeline runner
# ---------------------------------------------------------------------------


def patchify(cfg: DiTConfig, x: jnp.ndarray) -> jnp.ndarray:
    """NHWC latent [B, H, W, C] -> tokens [B, N, ps*ps*C], row-major over the
    token grid so a contiguous token range is a horizontal image band."""
    b, hgt, wid, c = x.shape
    ps = cfg.patch_size
    x = x.reshape(b, hgt // ps, ps, wid // ps, ps, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (hgt // ps) * (wid // ps), ps * ps * c)


def unpatchify(cfg: DiTConfig, tokens: jnp.ndarray, channels: int) -> jnp.ndarray:
    """tokens [B, N, ps*ps*C] -> NHWC [B, H, W, C]."""
    b, n, _ = tokens.shape
    ps = cfg.patch_size
    side_w = cfg.tokens_per_side
    side_h = n // side_w
    x = tokens.reshape(b, side_h, side_w, ps, ps, channels)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, side_h * ps, side_w * ps, channels)


def pos_embed_table(cfg: DiTConfig, dtype=jnp.float32) -> jnp.ndarray:
    """2D sin-cos position table [N, hidden] (diffusers convention: the
    FIRST half of the channels encodes the column/width coordinate, the
    second half the row — see the ordering note at the return).

    Coordinates follow diffusers' PatchEmbed scaling so converted PixArt
    weights see the frequencies they trained with:
    ``arange(side) / (side / base_size) / interpolation_scale`` — at the
    checkpoint's native size side == base_size, reducing to
    ``arange / interpolation_scale``."""
    h = cfg.hidden_size
    side = cfg.tokens_per_side
    dim = h // 2

    def axis_embed(pos, dim):
        omega = jnp.arange(dim // 2, dtype=jnp.float32)
        omega = 1.0 / (10000.0 ** (omega / (dim // 2)))
        out = pos[:, None] * omega[None, :]
        return jnp.concatenate([jnp.sin(out), jnp.cos(out)], axis=-1)

    base = cfg.pos_embed_base_size or side
    coords = (
        jnp.arange(side, dtype=jnp.float32)
        / (side / base)
        / cfg.interpolation_scale
    )
    row = axis_embed(coords, dim)  # [side, dim]
    col = axis_embed(coords, dim)
    grid_row = jnp.repeat(row, side, axis=0)            # [N, dim]
    grid_col = jnp.tile(col, (side, 1))                 # [N, dim]
    # Channel order matches diffusers get_2d_sincos_pos_embed: its
    # np.meshgrid(grid_w, grid_h)[0] is the WIDTH/column coordinate, and the
    # first half of the table is built from grid[0] — so column first.
    # Converted PixArt checkpoints trained against that layout; row-first
    # would transpose the positional table diagonally.
    return jnp.concatenate([grid_col, grid_row], axis=-1).astype(dtype)


def timestep_embedding(cfg: DiTConfig, t: jnp.ndarray) -> jnp.ndarray:
    """Sinusoidal timestep features [freq_dim] (DiT convention)."""
    half = cfg.frequency_embedding_size // 2
    freqs = jnp.exp(
        -math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half
    )
    args = t.astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


@jax.named_scope("time_embed")
def t_embed(params, cfg: DiTConfig, t: jnp.ndarray) -> jnp.ndarray:
    """Timestep -> conditioning vector [hidden]."""
    f = timestep_embedding(cfg, t).astype(params["t_fc1"]["kernel"].dtype)
    return linear(params["t_fc2"], silu(linear(params["t_fc1"], f)))


def size_condition_embed(
    params, cfg: DiTConfig, height: float, width: float
) -> jnp.ndarray:
    """PixArt micro-conditioning vector [hidden]: sinusoidal features of the
    original (height, width) and the aspect ratio, each through its own
    2-layer embedder, concatenated (so 3 * size_emb_dim == hidden)."""

    def embed(emb_p, vals):
        f = jnp.stack([
            timestep_embedding(cfg, jnp.asarray(v, jnp.float32)) for v in vals
        ])
        f = f.astype(emb_p["fc1"]["kernel"].dtype)
        return linear(emb_p["fc2"], silu(linear(emb_p["fc1"], f))).reshape(-1)

    res = embed(params["resolution_embedder"], (height, width))
    ar = embed(params["aspect_ratio_embedder"], (height / width,))
    return jnp.concatenate([res, ar])


def fold_size_condition(params, cfg: DiTConfig, height: float, width: float):
    """Return params with the micro-conditioning folded into ``t_fc2.bias``.

    The size embedding is timestep-independent and enters purely additively
    on t_embed's output — which feeds adaln_table AND final_layer — so
    adding it to the last bias is exact, costs nothing per step, and leaves
    every runner untouched.  No-op when the config (or checkpoint) has no
    additional conditions.
    """
    if not cfg.use_additional_conditions or "resolution_embedder" not in params:
        return params
    cond = size_condition_embed(params, cfg, height, width)
    out = dict(params)
    out["t_fc2"] = dict(params["t_fc2"])
    out["t_fc2"]["bias"] = params["t_fc2"]["bias"] + cond.astype(
        params["t_fc2"]["bias"].dtype
    )
    return out


def caption_project(params, enc: jnp.ndarray) -> jnp.ndarray:
    """Text-encoder states [B, Lt, caption_dim] -> [B, Lt, hidden]."""
    return linear(
        params["cap_fc2"],
        jax.nn.gelu(linear(params["cap_fc1"], enc), approximate=True),
    )


@jax.named_scope("adaln")
def adaln_table(params, cfg: DiTConfig, temb: jnp.ndarray) -> jnp.ndarray:
    """Global adaLN-single output for one timestep embedding: [6, hidden]."""
    return linear(params["adaln"], silu(temb)).reshape(6, cfg.hidden_size)


@jax.named_scope("layernorm")
def _ln(x):
    """LayerNorm without learnable affine (the modulation supplies it)."""
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + 1e-6)).astype(x.dtype)


def precompute_caption_kv(params, cfg: DiTConfig, enc: jnp.ndarray) -> jnp.ndarray:
    """Per-block cross-attention K/V, computed once per generation:
    [depth, B, Lt, 2*hidden].  The text tokens are constant across the
    denoise loop (same reasoning as the UNet's precompute_text_kv).

    Computed outside dit_forward, so it applies the model-dtype entry cast
    itself: fp32 caption embeds would otherwise yield fp32 KV whose
    cross-attention output upcasts the residual stream for every remaining
    block (the same silent 2x-HBM leak fixed in the UNet's cache)."""
    enc = enc.astype(params["cap_fc1"]["kernel"].dtype)
    y = caption_project(params, enc)
    return jax.vmap(lambda kvp: linear(kvp, y))(params["blocks"]["cross_kv"])


def caption_mask_bias(mask: jnp.ndarray) -> jnp.ndarray:
    """Tokenizer attention mask [..., Lt] (1 = real token) -> additive
    cross-attention bias [..., 1, 1, Lt].  PixArt masks padded T5 caption
    tokens out of cross-attention; a -1e9 logit offset removes a key exactly
    (its softmax weight underflows to 0)."""
    return jnp.where(mask[..., None, None, :].astype(bool), 0.0, -1e9).astype(
        jnp.float32
    )


@jax.named_scope("attn")
def _masked_cross_sdpa(q, k, v, bias, heads: int):
    """Cross-attention with an additive key bias.  Caption sequences are
    tiny (77-300 tokens) so the plain XLA einsum path is the right kernel;
    the flash kernels never engage for cross-attention anyway
    (ops/attention.py routes by key length)."""
    b, lq, c = q.shape
    lk = k.shape[1]
    d = c // heads
    qh = q.reshape(b, lq, heads, d)
    kh = k.reshape(b, lk, heads, d)
    vh = v.reshape(b, lk, heads, d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qh, kh,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    w = jax.nn.softmax(logits + bias, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), vh)
    return out.reshape(b, lq, c)


@jax.named_scope("block")
def dit_block(
    bp: Dict[str, Any],
    cfg: DiTConfig,
    x: jnp.ndarray,            # [B, Lq, hidden] — the tokens this call computes
    c6: jnp.ndarray,           # [6, hidden] adaLN-single for this timestep
    cap_kv: jnp.ndarray,       # [B, Lt, 2*hidden] precomputed text K/V
    self_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    patch_start: Optional[jnp.ndarray] = None,
    kv_assemble=None,
    attn_core=None,
    cap_bias: Optional[jnp.ndarray] = None,  # [B, 1, 1, Lt] additive
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """One transformer block.

    Self-attention K/V assembly is mode-pluggable — only this op ever
    crosses patch boundaries in a DiT (LayerNorm, MLP, and text
    cross-attention are per-token):

    * dense (``self_kv is None, kv_assemble is None``): attend over ``x``;
    * cache mode (``self_kv=(K, V)`` [B, N, hidden] + ``patch_start``):
      fresh K/V overwrite the ``Lq`` rows before attending — PipeFusion's
      newest-available cache (parallel/pipefusion.py);
    * hook mode (``kv_assemble``): ``(K, V) = kv_assemble(k, v)`` builds the
      attended KV any other way (fresh all-gather for the sync phase of
      displaced patch parallelism, carried-stale with a fresh own slot for
      its steady state — parallel/dit_sp.py);
    * core mode (``attn_core``): replaces the sdpa call entirely with
      ``attn_core(q, K, V) -> [B, Lq, hidden]`` — the ring-streamed online
      softmax uses this (parallel/dit_sp.py attn_impl="ring").

    Returns ``(x_out, (k, v))`` — the fresh local K/V, so runners can
    commit/exchange them.
    """
    table = bp["scale_shift_table"]  # [6, hidden]
    # c6 is [6, hidden] (one timestep) or [B, 6, hidden] (per-row timesteps,
    # packed cohort dispatch) — either way mods broadcasts over batch
    mods = table[None] + (c6[None] if c6.ndim == 2 else c6)
    s1, sc1, g1, s2, sc2, g2 = [mods[:, i][:, None, :] for i in range(6)]

    hn = _ln(x) * (1.0 + sc1) + s1
    q = linear(bp["attn_q"], hn)
    kv = linear(bp["attn_kv"], hn)
    k, v = jnp.split(kv, 2, axis=-1)
    if kv_assemble is not None:
        full_k, full_v = kv_assemble(k, v)
    elif self_kv is None:
        full_k, full_v = k, v
    else:
        full_k = lax.dynamic_update_slice(self_kv[0], k, (0, patch_start, 0))
        full_v = lax.dynamic_update_slice(self_kv[1], v, (0, patch_start, 0))
    if attn_core is None:
        att = sdpa(q, full_k, full_v, heads=cfg.num_heads)
    else:
        att = attn_core(q, full_k, full_v)
    x = x + g1 * linear(bp["attn_out"], att)

    cq = linear(bp["cross_q"], x)
    ck, cv = jnp.split(cap_kv, 2, axis=-1)
    if cap_bias is None:
        catt = sdpa(cq, ck, cv, heads=cfg.num_heads)
    else:
        catt = _masked_cross_sdpa(cq, ck, cv, cap_bias, cfg.num_heads)
    x = x + linear(bp["cross_out"], catt)

    hn2 = _ln(x) * (1.0 + sc2) + s2
    with jax.named_scope("ff"):
        x = x + g2 * linear(
            bp["mlp_fc2"],
            jax.nn.gelu(linear(bp["mlp_fc1"], hn2), approximate=True)
        )
    return x, (k, v)


def final_layer(params, cfg: DiTConfig, x: jnp.ndarray, temb: jnp.ndarray) -> jnp.ndarray:
    """Final modulated projection: [B, L, hidden] -> [B, L, ps*ps*out_ch].

    Modulation = learned 2-entry table + the timestep embedding (PixArt's
    T2IFinalLayer shape: table-plus-conditioning, no extra projection).
    """
    if temb.ndim == 1:
        mods = params["final_table"] + temb[None]    # [2, hidden]
        shift, scale = mods[0][None, None], mods[1][None, None]
    else:  # per-row timesteps (packed cohort dispatch): temb [B, hidden]
        mods = params["final_table"][None] + temb[:, None]  # [B, 2, hidden]
        shift, scale = mods[:, 0][:, None], mods[:, 1][:, None]
    h = _ln(x) * (1.0 + scale) + shift
    return linear(params["final_out"], h)


def embed_tokens(params, cfg: DiTConfig, tokens: jnp.ndarray,
                 pos: jnp.ndarray) -> jnp.ndarray:
    """Patchified latent tokens [B, L, ps*ps*C] (+ their pos rows [L, hidden])
    -> block-space activations."""
    return linear(params["proj_in"], tokens) + pos[None].astype(tokens.dtype)


# ---------------------------------------------------------------------------
# Dense forward (single device / full sequence)
# ---------------------------------------------------------------------------


def dit_forward(
    params: Dict[str, Any],
    cfg: DiTConfig,
    x: jnp.ndarray,                  # [B, H, W, C] NHWC latent
    t: jnp.ndarray,                  # scalar timestep
    enc: jnp.ndarray,                # [B, Lt, caption_dim]
    cap_kv: Optional[jnp.ndarray] = None,   # [depth, B, Lt, 2*hidden]
    cap_mask: Optional[jnp.ndarray] = None,  # [B, Lt], 1 = real token
) -> jnp.ndarray:
    """Full DiT evaluation; returns the epsilon prediction as NHWC."""
    tokens = patchify(cfg, x).astype(params["proj_in"]["kernel"].dtype)
    pos = pos_embed_table(cfg, tokens.dtype)
    h = embed_tokens(params, cfg, tokens, pos)
    temb = t_embed(params, cfg, t)
    c6 = adaln_table(params, cfg, temb)
    if cap_kv is None:
        cap_kv = precompute_caption_kv(params, cfg, enc)
    cap_bias = None if cap_mask is None else caption_mask_bias(cap_mask)

    def body(hc, xs):
        bp, kv = xs
        out, _ = dit_block(bp, cfg, hc, c6, kv, cap_bias=cap_bias)
        return out, None

    h, _ = lax.scan(body, h, (params["blocks"], cap_kv))
    out_tokens = final_layer(params, cfg, h, temb)
    return unpatchify(cfg, out_tokens.astype(jnp.float32), cfg.out_channels)
