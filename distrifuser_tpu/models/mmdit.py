"""Functional MMDiT (Stable Diffusion 3-class joint transformer) in JAX.

The reference framework targets the SD/SDXL UNet only; this module extends
the same displaced-patch machinery to the *current* diffusion architecture
— the multimodal DiT of "Scaling Rectified Flow Transformers for
High-Resolution Image Synthesis" (Esser et al., 2024; SD3): two token
streams (text context, image patches) with per-stream adaLN modulation and
weights, attending JOINTLY (queries/keys/values of both streams are
concatenated along the token axis into one attention call per block).

TPU-first layout mirrors models/dit.py:

* all ``depth`` blocks are one stacked param pytree (leading ``[depth]``
  axis) consumed by ``lax.scan`` — uniform shapes, one compiled block body;
* activations are token-major ``[B, N, hidden]``; a contiguous token range
  is a horizontal latent band, so the displaced-patch runner shards rows by
  slicing tokens (parallel/mmdit_sp.py);
* the attention core is ops.attention.sdpa (Pallas flash on TPU for long
  joint sequences, chunked XLA otherwise).

Deliberate simplifications, documented for checkpoint converters:

* The final block keeps a full context stream (SD3 drops the context
  attn-out/MLP in its last block, "context_pre_only"); the extra outputs
  are computed and DISCARDED, so numerics match — the stacked-scan layout
  needs uniform leaves, and the converter zero-fills the unused tail
  weights (models/weights.py convert_mmdit_state_dict).
* q/k RMSNorm is config-gated (``qk_norm``): off for SD3.0-2B, per-head
  RMS with learned weights for the SD3.5 family (diffusers
  qk_norm="rms_norm").
* SD3.5-medium's dual_attention_layers (an EXTRA image-stream-only
  self-attention per early block, diffusers use_dual_attention) is
  supported for the published contiguous-prefix layout: blocks
  [0, dual_attention_blocks) carry the second attention.  The stacked-scan
  layout splits into TWO scans (dual prefix, plain suffix) so each body
  compiles once with uniform leaves; the dual extras live in a separate
  ``blocks_dual`` stacked pytree (``x_mod2`` = the LAST 3 chunks of
  diffusers' 9-chunk AdaLayerNormZeroX, fused ``x2_qkv``, ``x2_out``,
  and qk-norm weights).  Non-prefix dual layouts are rejected loudly.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import sdpa
from ..ops.linear import linear
from .dit import _init_linear, _ln, timestep_embedding

silu = jax.nn.silu


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MMDiTConfig:
    """Static architecture description (SD3-class MMDiT)."""

    sample_size: int = 128          # latent H = W (1024 px / 8)
    patch_size: int = 2
    in_channels: int = 16
    out_channels: int = 16
    hidden_size: int = 1536         # SD3-medium: 24 heads * 64
    depth: int = 24
    num_heads: int = 24
    mlp_ratio: int = 4
    joint_attention_dim: int = 4096  # context width (T5-XXL / CLIP concat)
    pooled_projection_dim: int = 2048  # CLIP-L + bigG pooled concat
    frequency_embedding_size: int = 256
    # sin-cos table is built on a pos_embed_max_size grid and center-cropped
    # to the actual token grid (SD3 PatchEmbed semantics) so one checkpoint
    # serves multiple resolutions
    pos_embed_max_size: int = 192
    # SD3.5 family: RMS-normalize per-head q/k in both streams before the
    # joint attention (diffusers qk_norm="rms_norm"); SD3.0 leaves it off
    qk_norm: bool = False
    # SD3.5-medium: blocks [0, dual_attention_blocks) run a SECOND
    # image-stream-only self-attention (diffusers dual_attention_layers,
    # a contiguous prefix in every published checkpoint)
    dual_attention_blocks: int = 0

    @property
    def tokens_per_side(self) -> int:
        return self.sample_size // self.patch_size

    @property
    def num_tokens(self) -> int:
        return self.tokens_per_side ** 2

    @property
    def token_dim(self) -> int:
        return self.patch_size * self.patch_size * self.in_channels

    @property
    def token_out_dim(self) -> int:
        return self.patch_size * self.patch_size * self.out_channels

    def __post_init__(self):
        if self.sample_size % self.patch_size != 0:
            raise ValueError("sample_size must be divisible by patch_size")
        if self.hidden_size % self.num_heads != 0:
            raise ValueError("hidden_size must be divisible by num_heads")
        if self.tokens_per_side > self.pos_embed_max_size:
            raise ValueError(
                f"token grid {self.tokens_per_side} exceeds "
                f"pos_embed_max_size {self.pos_embed_max_size}"
            )
        if not 0 <= self.dual_attention_blocks <= self.depth:
            raise ValueError(
                f"dual_attention_blocks={self.dual_attention_blocks} must "
                f"lie in [0, depth={self.depth}]"
            )


def sd3_config(sample_size: int = 128) -> MMDiTConfig:
    """SD3-medium geometry (2B): depth 24, hidden 1536, 16-channel latent."""
    return MMDiTConfig(sample_size=sample_size)


def mmdit_config_from_json(source) -> MMDiTConfig:
    """Config from a diffusers SD3Transformer2DModel config.json (dict or
    path), rejecting architecture options this module does not implement."""
    cfg = source
    if not isinstance(source, dict):
        with open(source) as f:
            cfg = json.load(f)
    if cfg.get("qk_norm") not in (None, "", False, "rms_norm"):
        raise ValueError(
            f"qk_norm={cfg.get('qk_norm')!r}: only the SD3.5 family's "
            "'rms_norm' is implemented; refusing to load silently-wrong "
            "weights"
        )
    dual = tuple(cfg.get("dual_attention_layers") or ())
    if dual != tuple(range(len(dual))):
        raise ValueError(
            f"dual_attention_layers={dual}: only the published "
            "contiguous-prefix layout (0, 1, ..., k-1; SD3.5-medium uses "
            "0-12) is implemented — refusing an unknown block layout"
        )
    head_dim = cfg.get("attention_head_dim", 64)
    heads = cfg.get("num_attention_heads", 24)
    return MMDiTConfig(
        dual_attention_blocks=len(dual),
        sample_size=cfg.get("sample_size", 128),
        patch_size=cfg.get("patch_size", 2),
        in_channels=cfg.get("in_channels", 16),
        out_channels=cfg.get("out_channels", cfg.get("in_channels", 16)),
        hidden_size=heads * head_dim,
        depth=cfg.get("num_layers", 24),
        num_heads=heads,
        joint_attention_dim=cfg.get("joint_attention_dim", 4096),
        pooled_projection_dim=cfg.get("pooled_projection_dim", 2048),
        pos_embed_max_size=cfg.get("pos_embed_max_size", 192),
        qk_norm=cfg.get("qk_norm") == "rms_norm",
    )


def tiny_mmdit_config(depth: int = 4) -> MMDiTConfig:
    """Test-scale geometry: 16x16 latent grid, width 32."""
    return MMDiTConfig(
        sample_size=32,
        patch_size=2,
        in_channels=4,
        out_channels=4,
        hidden_size=32,
        depth=depth,
        num_heads=4,
        mlp_ratio=2,
        joint_attention_dim=32,
        pooled_projection_dim=24,
        pos_embed_max_size=64,
    )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_block(key, cfg: MMDiTConfig, dtype):
    h = cfg.hidden_size
    keys = jax.random.split(key, 10)
    block = {
        # per-stream adaLN: 6 modulation vectors each (shift/scale/gate for
        # attention and MLP), from silu(conditioning vec)
        "x_mod": _init_linear(keys[0], h, 6 * h, dtype),
        "c_mod": _init_linear(keys[1], h, 6 * h, dtype),
        "x_qkv": _init_linear(keys[2], h, 3 * h, dtype),
        "c_qkv": _init_linear(keys[3], h, 3 * h, dtype),
        "x_out": _init_linear(keys[4], h, h, dtype),
        "c_out": _init_linear(keys[5], h, h, dtype),
        "x_fc1": _init_linear(keys[6], h, cfg.mlp_ratio * h, dtype),
        "x_fc2": _init_linear(keys[7], cfg.mlp_ratio * h, h, dtype),
        "c_fc1": _init_linear(keys[8], h, cfg.mlp_ratio * h, dtype),
        "c_fc2": _init_linear(keys[9], cfg.mlp_ratio * h, h, dtype),
    }
    if cfg.qk_norm:
        d = h // cfg.num_heads
        for name in ("x_qnorm", "x_knorm", "c_qnorm", "c_knorm"):
            block[name] = jnp.ones((d,), dtype)  # RMSNorm weight init
    return block


def _init_dual_block(key, cfg: MMDiTConfig, dtype):
    """Extra leaves for one dual-attention block (SD3.5-medium): the
    second image-stream self-attention and its 3 modulation vectors (the
    last 3 chunks of diffusers' 9-chunk AdaLayerNormZeroX)."""
    h = cfg.hidden_size
    keys = jax.random.split(key, 3)
    block = {
        "x_mod2": _init_linear(keys[0], h, 3 * h, dtype),
        "x2_qkv": _init_linear(keys[1], h, 3 * h, dtype),
        "x2_out": _init_linear(keys[2], h, h, dtype),
    }
    if cfg.qk_norm:
        d = h // cfg.num_heads
        block["x2_qnorm"] = jnp.ones((d,), dtype)
        block["x2_knorm"] = jnp.ones((d,), dtype)
    return block


def init_mmdit_params(key, cfg: MMDiTConfig, dtype=jnp.float32) -> Dict[str, Any]:
    """Random-init parameter pytree; ``blocks`` leaves carry a leading
    ``[depth]`` axis for lax.scan / stage sharding."""
    h = cfg.hidden_size
    keys = jax.random.split(key, 8)
    blocks = jax.vmap(lambda k: _init_block(k, cfg, dtype))(
        jax.random.split(keys[7], cfg.depth)
    )
    extra = {}
    if cfg.dual_attention_blocks:
        extra["blocks_dual"] = jax.vmap(
            lambda k: _init_dual_block(k, cfg, dtype)
        )(jax.random.split(jax.random.fold_in(keys[7], 1),
                           cfg.dual_attention_blocks))
    return {
        **extra,
        "proj_in": _init_linear(keys[0], cfg.token_dim, h, dtype),
        "ctx_in": _init_linear(keys[1], cfg.joint_attention_dim, h, dtype),
        "t_fc1": _init_linear(keys[2], cfg.frequency_embedding_size, h, dtype),
        "t_fc2": _init_linear(jax.random.fold_in(keys[2], 1), h, h, dtype),
        "pool_fc1": _init_linear(keys[3], cfg.pooled_projection_dim, h, dtype),
        "pool_fc2": _init_linear(jax.random.fold_in(keys[3], 1), h, h, dtype),
        "final_mod": _init_linear(keys[4], h, 2 * h, dtype),
        "final_out": _init_linear(keys[5], h, cfg.token_out_dim, dtype),
        "blocks": blocks,
    }


# ---------------------------------------------------------------------------
# Pieces (shared with the SP runner)
# ---------------------------------------------------------------------------


def pos_embed_cropped(cfg: MMDiTConfig, dtype=jnp.float32) -> jnp.ndarray:
    """[N, hidden] sin-cos table: built on the pos_embed_max_size grid,
    center-cropped to the actual tokens_per_side window (SD3 PatchEmbed).
    Channel order follows the same column-first convention as
    dit.pos_embed_table, and coordinates follow the diffusers PatchEmbed
    scaling ``arange(max) * base_size / max`` with base_size = the
    config's token grid side — the frequency the checkpoint trained with
    (same normalization family as dit.pos_embed_table's
    interpolation_scale handling)."""
    h = cfg.hidden_size
    side = cfg.tokens_per_side
    big = cfg.pos_embed_max_size
    dim = h // 2

    def axis_embed(pos, dim):
        omega = jnp.arange(dim // 2, dtype=jnp.float32)
        omega = 1.0 / (10000.0 ** (omega / (dim // 2)))
        out = pos[:, None] * omega[None, :]
        return jnp.concatenate([jnp.sin(out), jnp.cos(out)], axis=-1)

    coords = jnp.arange(big, dtype=jnp.float32) * (side / big)
    emb = axis_embed(coords, dim)                    # [big, dim]
    top = (big - side) // 2
    row = lax.dynamic_slice_in_dim(emb, top, side, 0)   # rows window
    col = lax.dynamic_slice_in_dim(emb, top, side, 0)   # square latents
    grid_row = jnp.repeat(row, side, axis=0)         # [N, dim]
    grid_col = jnp.tile(col, (side, 1))
    return jnp.concatenate([grid_col, grid_row], axis=-1).astype(dtype)


def cond_vec(params, cfg: MMDiTConfig, t: jnp.ndarray,
             pooled: jnp.ndarray) -> jnp.ndarray:
    """Conditioning vector [B, hidden] = MLP(t features) + MLP(pooled text).

    ``t`` broadcasts over batch (scalar or [B]); SD3 feeds the flow sigma
    scaled by 1000 as the "timestep"."""
    t = jnp.atleast_1d(jnp.asarray(t, jnp.float32))
    f = jax.vmap(lambda ti: timestep_embedding(cfg, ti))(t)
    f = f.astype(params["t_fc1"]["kernel"].dtype)
    temb = linear(params["t_fc2"], silu(linear(params["t_fc1"], f)))
    p = pooled.astype(params["pool_fc1"]["kernel"].dtype)
    pemb = linear(params["pool_fc2"], silu(linear(params["pool_fc1"], p)))
    if temb.shape[0] == 1 and pemb.shape[0] != 1:
        temb = jnp.broadcast_to(temb, pemb.shape)
    return temb + pemb


@jax.named_scope("adaln")
def _mods(mod_p, vec, n):
    """silu(vec) -> n modulation vectors, each [B, 1, hidden]."""
    m = linear(mod_p, silu(vec))
    return [c[:, None, :] for c in jnp.split(m, n, axis=-1)]


def _rms_heads(x, w, heads: int):
    """Per-head RMSNorm over head_dim (SD3.5 qk_norm, fp32 moments):
    [B, L, C] with weight [C/heads] -> [B, L, C]."""
    b, l, c = x.shape
    d = c // heads
    xh = x.reshape(b, l, heads, d).astype(jnp.float32)
    y = xh * lax.rsqrt(jnp.mean(xh * xh, axis=-1, keepdims=True) + 1e-6)
    return (y * w.astype(jnp.float32)).astype(x.dtype).reshape(b, l, c)


@jax.named_scope("block")
def mmdit_block(
    bp: Dict[str, Any],
    cfg: MMDiTConfig,
    x: jnp.ndarray,               # [B, Lx, hidden] image tokens (local rows)
    ctx: jnp.ndarray,             # [B, Lc, hidden] context tokens
    vec: jnp.ndarray,             # [B, hidden] conditioning
    kv_assemble=None,
    attn_core=None,
    dual_p: Optional[Dict[str, Any]] = None,
    kv2_assemble=None,
    attn2_core=None,
):
    """One joint-attention block.

    Queries/keys/values of both streams concatenate along tokens (context
    rows first — an internal ordering choice; attention output is
    invariant to key order and equivariant to query order, so it carries
    no checkpoint-compat meaning) into one sdpa call; each stream keeps
    its own projections, modulation, and MLP.

    ``kv_assemble(xk, xv) -> (K, V)`` is the displaced-patch hook, the
    analog of dit.dit_block's: it builds the IMAGE-stream KV any other way
    (all-gather across patch peers for the sync phase, carried-stale with
    the fresh own slot in the steady state).  The context KV never needs
    assembly — every device computes the full (replicated) context stream.

    ``attn_core(cq, xq, (ck, cv), (xk, xv)) -> [B, Lc+Lx, hidden]``
    replaces the whole attention call — the ring-streamed online softmax
    uses this (parallel/mmdit_sp.py attn_impl="ring").  Mutually exclusive
    with ``kv_assemble``.

    ``dual_p`` (SD3.5-medium dual attention) adds a SECOND image-only
    self-attention: its input is the same pre-attention LayerNorm of ``x``
    modulated by ``x_mod2``'s (shift, scale, gate) — diffusers
    AdaLayerNormZeroX's last 3 chunks — and its gated output is added
    AFTER the joint-attention residual.  ``kv2_assemble``/``attn2_core``
    are its displaced-patch hooks, same contracts as above but image-only
    (attn2_core receives ``(q2, (k2, v2)) -> [B, Lx, hidden]``).

    Returns ``(x_out, ctx_out, (xk, xv))`` with the fresh local image KV —
    plus a trailing ``(k2, v2)`` element when ``dual_p`` is given.
    """
    assert kv_assemble is None or attn_core is None
    xs1, xsc1, xg1, xs2, xsc2, xg2 = _mods(bp["x_mod"], vec, 6)
    cs1, csc1, cg1, cs2, csc2, cg2 = _mods(bp["c_mod"], vec, 6)

    xln = _ln(x)
    xn = xln * (1.0 + xsc1) + xs1
    cn = _ln(ctx) * (1.0 + csc1) + cs1
    xq, xk, xv = jnp.split(linear(bp["x_qkv"], xn), 3, axis=-1)
    cq, ck, cv = jnp.split(linear(bp["c_qkv"], cn), 3, axis=-1)
    if "x_qnorm" in bp:  # SD3.5 qk_norm (cfg.qk_norm param layout)
        xq = _rms_heads(xq, bp["x_qnorm"], cfg.num_heads)
        xk = _rms_heads(xk, bp["x_knorm"], cfg.num_heads)
        cq = _rms_heads(cq, bp["c_qnorm"], cfg.num_heads)
        ck = _rms_heads(ck, bp["c_knorm"], cfg.num_heads)

    if attn_core is not None:
        att = attn_core(cq, xq, (ck, cv), (xk, xv))
    else:
        if kv_assemble is not None:
            full_xk, full_xv = kv_assemble(xk, xv)
        else:
            full_xk, full_xv = xk, xv
        q = jnp.concatenate([cq, xq], axis=1)
        k = jnp.concatenate([ck, full_xk], axis=1)
        v = jnp.concatenate([cv, full_xv], axis=1)
        att = sdpa(q, k, v, heads=cfg.num_heads)
    lc = ctx.shape[1]
    catt, xatt = att[:, :lc], att[:, lc:]

    x = x + xg1 * linear(bp["x_out"], xatt)
    ctx = ctx + cg1 * linear(bp["c_out"], catt)

    kv2 = None
    if dual_p is not None:
        assert kv2_assemble is None or attn2_core is None
        d_s, d_sc, d_g = _mods(dual_p["x_mod2"], vec, 3)
        xn2a = xln * (1.0 + d_sc) + d_s
        q2, k2, v2 = jnp.split(linear(dual_p["x2_qkv"], xn2a), 3, axis=-1)
        if "x2_qnorm" in dual_p:
            q2 = _rms_heads(q2, dual_p["x2_qnorm"], cfg.num_heads)
            k2 = _rms_heads(k2, dual_p["x2_knorm"], cfg.num_heads)
        if attn2_core is not None:
            att2 = attn2_core(q2, (k2, v2))
        else:
            fk2, fv2 = (kv2_assemble(k2, v2) if kv2_assemble is not None
                        else (k2, v2))
            att2 = sdpa(q2, fk2, fv2, heads=cfg.num_heads)
        # diffusers residual order: joint-attention output first (above),
        # then the gated dual output, then the MLP
        x = x + d_g * linear(dual_p["x2_out"], att2)
        kv2 = (k2, v2)

    xn2 = _ln(x) * (1.0 + xsc2) + xs2
    with jax.named_scope("ff"):
        x = x + xg2 * linear(
            bp["x_fc2"],
            jax.nn.gelu(linear(bp["x_fc1"], xn2), approximate=True)
        )
    cn2 = _ln(ctx) * (1.0 + csc2) + cs2
    with jax.named_scope("ff"):
        ctx = ctx + cg2 * linear(
            bp["c_fc2"],
            jax.nn.gelu(linear(bp["c_fc1"], cn2), approximate=True)
        )
    if dual_p is not None:
        return x, ctx, (xk, xv), kv2
    return x, ctx, (xk, xv)


def final_layer(params, cfg: MMDiTConfig, x: jnp.ndarray,
                vec: jnp.ndarray) -> jnp.ndarray:
    """adaLN-modulated projection [B, L, hidden] -> [B, L, ps*ps*out_ch]."""
    shift, scale = _mods(params["final_mod"], vec, 2)
    h = _ln(x) * (1.0 + scale) + shift
    return linear(params["final_out"], h)


# ---------------------------------------------------------------------------
# Dense forward (single device / full sequence)
# ---------------------------------------------------------------------------


def mmdit_forward(
    params: Dict[str, Any],
    cfg: MMDiTConfig,
    x: jnp.ndarray,                  # [B, H, W, C] NHWC latent
    t: jnp.ndarray,                  # scalar or [B]: flow sigma * 1000
    enc: jnp.ndarray,                # [B, Lc, joint_attention_dim]
    pooled: jnp.ndarray,             # [B, pooled_projection_dim]
) -> jnp.ndarray:
    """Full MMDiT evaluation; returns the velocity prediction as NHWC."""
    from .dit import patchify, unpatchify

    dtype = params["proj_in"]["kernel"].dtype
    tokens = patchify(cfg, x).astype(dtype)
    h = linear(params["proj_in"], tokens) + pos_embed_cropped(cfg, dtype)[None]
    ctx = linear(params["ctx_in"], enc.astype(dtype))
    vec = cond_vec(params, cfg, t, pooled)

    def body(carry, bp):
        hx, hc = carry
        hx, hc, _ = mmdit_block(bp, cfg, hx, hc, vec)
        return (hx, hc), None

    k = cfg.dual_attention_blocks
    if k:
        def body_dual(carry, xs):
            bp, dp = xs
            hx, hc = carry
            hx, hc, _, _ = mmdit_block(bp, cfg, hx, hc, vec, dual_p=dp)
            return (hx, hc), None

        prefix = jax.tree.map(lambda l: l[:k], params["blocks"])
        (h, ctx), _ = lax.scan(
            body_dual, (h, ctx), (prefix, params["blocks_dual"])
        )
        rest = jax.tree.map(lambda l: l[k:], params["blocks"])
    else:
        rest = params["blocks"]
    (h, _), _ = lax.scan(body, (h, ctx), rest)
    out = final_layer(params, cfg, h, vec)
    return unpatchify(cfg, out.astype(jnp.float32), cfg.out_channels)
