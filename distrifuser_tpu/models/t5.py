"""Native T5 text encoder (encoder-only) in JAX.

PixArt-alpha conditions on T5-v1.1-XXL hidden states (arXiv 2310.00426 §2.4)
the way SD/SDXL condition on CLIP; the reference imports its text encoders
from transformers (/root/reference/distrifuser/pipelines.py:26-28), so the
TPU framework carries its own, config.json-driven like models/clip.py.

Architecture (transformers ``T5EncoderModel`` semantics, parity-tested
weight-free in tests/test_t5.py):

* RMSNorm (no mean subtraction, fp32 moments) before each sublayer, final
  RMSNorm after the stack; residuals around both sublayers.
* Self-attention WITHOUT 1/sqrt(d) scaling (T5 folds it into init) plus a
  learned relative-position bias: bucketed log-spaced offsets, embedding
  owned by layer 0 and shared by every layer.
* Feed-forward either gated (v1.1: ``wo(act(wi_0 x) * (wi_1 x))``) or plain
  (``wo(act(wi x))``) per ``feed_forward_proj``.
* No biases anywhere; embedding is the ``shared`` table.

The stacked-blocks layout matches models/dit.py: every layer's leaves carry
a leading ``[num_layers]`` axis and the stack runs under ``lax.scan`` — one
compiled block program, weights sharded or replicated by the caller's mesh.
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

from ..ops.linear import linear


@dataclasses.dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32128
    d_model: int = 4096
    d_kv: int = 64
    d_ff: int = 10240
    num_layers: int = 24
    num_heads: int = 64
    relative_attention_num_buckets: int = 32
    relative_attention_max_distance: int = 128
    layer_norm_epsilon: float = 1e-6
    feed_forward_proj: str = "gated-gelu"

    @property
    def inner_dim(self) -> int:
        return self.num_heads * self.d_kv

    @property
    def is_gated(self) -> bool:
        return self.feed_forward_proj.startswith("gated")

    @property
    def act(self):
        name = self.feed_forward_proj.split("-")[-1]
        if name == "gelu":
            # transformers maps T5 "gelu" to gelu_new (tanh approximation)
            return lambda x: jax.nn.gelu(x, approximate=True)
        if name == "relu":
            return jax.nn.relu
        raise ValueError(f"unsupported feed_forward_proj {name!r}")


def t5_v1_1_xxl_config() -> T5Config:
    """google/t5-v1_1-xxl encoder geometry — PixArt-alpha's text encoder."""
    return T5Config()


def tiny_t5_config(gated: bool = True) -> T5Config:
    return T5Config(
        vocab_size=128, d_model=32, d_kv=8, d_ff=48, num_layers=3,
        num_heads=4,
        feed_forward_proj="gated-gelu" if gated else "relu",
    )


def t5_config_from_json(source) -> T5Config:
    """Build from a transformers T5Config config.json (path or dict)."""
    if isinstance(source, (str, os.PathLike)):
        with open(source) as f:
            source = json.load(f)
    d = dict(source)
    return T5Config(
        vocab_size=d.get("vocab_size", 32128),
        d_model=d.get("d_model", 4096),
        d_kv=d.get("d_kv", 64),
        d_ff=d.get("d_ff", 10240),
        num_layers=d.get("num_layers", 24),
        num_heads=d.get("num_heads", 64),
        relative_attention_num_buckets=d.get("relative_attention_num_buckets", 32),
        relative_attention_max_distance=d.get("relative_attention_max_distance", 128),
        layer_norm_epsilon=d.get("layer_norm_epsilon", 1e-6),
        feed_forward_proj=d.get("feed_forward_proj", "gated-gelu"),
    )


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * scale


def relative_position_buckets(cfg: T5Config, length: int) -> jnp.ndarray:
    """[Lq, Lk] bucket ids, bidirectional T5 bucketing: exact small offsets,
    log-spaced large ones, sign carried in the top half of the buckets."""
    n_buckets = cfg.relative_attention_num_buckets // 2
    max_dist = cfg.relative_attention_max_distance
    ctx = jnp.arange(length)
    rel = ctx[None, :] - ctx[:, None]  # memory - query
    buckets = jnp.where(rel > 0, n_buckets, 0)
    rel = jnp.abs(rel)
    max_exact = n_buckets // 2
    is_small = rel < max_exact
    rel_large = max_exact + (
        jnp.log(rel.astype(jnp.float32) / max_exact + 1e-9)
        / math.log(max_dist / max_exact)
        * (n_buckets - max_exact)
    ).astype(jnp.int32)
    rel_large = jnp.minimum(rel_large, n_buckets - 1)
    return buckets + jnp.where(is_small, rel, rel_large)


def _attention(lp, cfg: T5Config, x, pos_bias, mask_bias):
    """T5 self-attention: unscaled logits + shared relative-position bias."""
    b, l, _ = x.shape
    h, dk = cfg.num_heads, cfg.d_kv
    q = linear(lp["q"], x).reshape(b, l, h, dk)
    k = linear(lp["k"], x).reshape(b, l, h, dk)
    v = linear(lp["v"], x).reshape(b, l, h, dk)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    logits = logits + pos_bias[None] + mask_bias
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    att = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, l, cfg.inner_dim)
    return linear(lp["o"], att)


def _ff(lp, cfg: T5Config, x):
    if cfg.is_gated:
        return linear(lp["wo"], cfg.act(linear(lp["wi_0"], x)) * linear(lp["wi_1"], x))
    return linear(lp["wo"], cfg.act(linear(lp["wi"], x)))


@jax.named_scope("text_encoder")
def t5_encode(
    params: Dict[str, Any],
    cfg: T5Config,
    input_ids: jnp.ndarray,                  # [B, L] int32
    attention_mask: Optional[jnp.ndarray] = None,  # [B, L] 1=keep
) -> jnp.ndarray:
    """Token ids -> final hidden states [B, L, d_model]."""
    x = params["shared"][input_ids]
    l = input_ids.shape[1]
    pos_bias = jnp.einsum(
        "qkb,bh->hqk",
        jax.nn.one_hot(
            relative_position_buckets(cfg, l),
            cfg.relative_attention_num_buckets,
            dtype=jnp.float32,
        ),
        params["relative_attention_bias"].astype(jnp.float32),
    )  # [heads, L, L]
    if attention_mask is None:
        mask_bias = jnp.zeros((1, 1, 1, l), jnp.float32)
    else:
        mask_bias = jnp.where(
            attention_mask[:, None, None, :].astype(bool), 0.0, -1e9
        ).astype(jnp.float32)
    eps = cfg.layer_norm_epsilon

    def body(h, lp):
        h = h + _attention(
            lp["attn"], cfg, _rms_norm(h, lp["attn_norm"], eps), pos_bias, mask_bias
        )
        h = h + _ff(lp["ff"], cfg, _rms_norm(h, lp["ff_norm"], eps))
        return h, None

    x, _ = lax.scan(body, x, params["layers"])
    return _rms_norm(x, params["final_norm"], eps)


# ---------------------------------------------------------------------------
# init (tests / structural use)
# ---------------------------------------------------------------------------


def init_t5_params(key, cfg: T5Config, dtype=jnp.float32) -> Dict[str, Any]:
    keys = jax.random.split(key, 4)

    def lin(k, cin, cout):
        return {"kernel": jax.random.normal(k, (cin, cout), dtype) / math.sqrt(cin)}

    def layer(k):
        ks = jax.random.split(k, 6)
        ff = (
            {"wi_0": lin(ks[3], cfg.d_model, cfg.d_ff),
             "wi_1": lin(ks[4], cfg.d_model, cfg.d_ff),
             "wo": lin(ks[5], cfg.d_ff, cfg.d_model)}
            if cfg.is_gated
            else {"wi": lin(ks[3], cfg.d_model, cfg.d_ff),
                  "wo": lin(ks[5], cfg.d_ff, cfg.d_model)}
        )
        return {
            "attn": {
                "q": lin(ks[0], cfg.d_model, cfg.inner_dim),
                "k": lin(ks[1], cfg.d_model, cfg.inner_dim),
                "v": lin(ks[2], cfg.d_model, cfg.inner_dim),
                "o": lin(jax.random.fold_in(k, 9), cfg.inner_dim, cfg.d_model),
            },
            "attn_norm": jnp.ones((cfg.d_model,), dtype),
            "ff": ff,
            "ff_norm": jnp.ones((cfg.d_model,), dtype),
        }

    layer_keys = jax.random.split(keys[2], cfg.num_layers)
    return {
        "shared": jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model), dtype),
        "relative_attention_bias": jax.random.normal(
            keys[1], (cfg.relative_attention_num_buckets, cfg.num_heads), dtype
        ),
        "layers": jax.vmap(layer)(layer_keys),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
