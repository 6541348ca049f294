"""CLIP text encoders in JAX (SD's ViT-L and SDXL's additional OpenCLIP bigG).

The reference gets these for free from the diffusers pipeline it wraps
(SURVEY.md §1: text encoders run replicated on every rank, only the UNet is
swapped — /root/reference/distrifuser/pipelines.py:39-42).  The TPU build
needs its own: a standard pre-LN transformer with causal masking, quick-GeLU
(ViT-L) or GeLU (bigG) MLPs, EOS-token pooling, and an optional
text_projection (bigG).  SDXL consumes the *penultimate* hidden state of both
encoders plus the projected pooled output of the second; SD 1.x consumes the
final hidden state — so the forward returns all hidden states.

Parity target: transformers' torch `CLIPTextModel` (tested against it with
random weights in tests/test_clip.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..ops.linear import linear
from .unet import layer_norm


@dataclasses.dataclass(frozen=True)
class CLIPTextConfig:
    vocab_size: int = 49408
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 77
    hidden_act: str = "quick_gelu"  # "gelu" for OpenCLIP bigG
    eos_token_id: int = 49407
    projection_dim: Optional[int] = None  # set for SDXL text_encoder_2


def clip_vit_l_config() -> CLIPTextConfig:
    """openai/clip-vit-large-patch14 — SD 1.x / SDXL text_encoder."""
    return CLIPTextConfig()


def open_clip_bigg_config() -> CLIPTextConfig:
    """laion/CLIP-ViT-bigG-14 — SDXL text_encoder_2 (penultimate + projection)."""
    return CLIPTextConfig(
        hidden_size=1280,
        num_hidden_layers=32,
        num_attention_heads=20,
        intermediate_size=5120,
        hidden_act="gelu",
        projection_dim=1280,
    )


def open_clip_vith_config() -> CLIPTextConfig:
    """OpenCLIP ViT-H/14 text tower as shipped in SD 2.x snapshots
    (23 transformer layers — diffusers stores the truncated penultimate-layer
    variant — GeLU MLPs, final hidden state consumed)."""
    return CLIPTextConfig(
        hidden_size=1024,
        num_hidden_layers=23,
        num_attention_heads=16,
        intermediate_size=4096,
        hidden_act="gelu",
    )


def clip_config_from_json(source) -> CLIPTextConfig:
    """Build a CLIPTextConfig from a transformers `text_encoder/config.json`
    (path or dict).  `projection_dim` is honored only when the stored
    architecture is CLIPTextModelWithProjection (SDXL's text_encoder_2) —
    plain CLIPTextModel snapshots carry the field too, but no
    text_projection weights exist to apply it."""
    from .unet import load_config_source

    cfg = load_config_source(source)
    with_projection = "CLIPTextModelWithProjection" in (
        cfg.get("architectures") or []
    )
    return CLIPTextConfig(
        vocab_size=cfg.get("vocab_size", 49408),
        hidden_size=cfg.get("hidden_size", 768),
        num_hidden_layers=cfg.get("num_hidden_layers", 12),
        num_attention_heads=cfg.get("num_attention_heads", 12),
        intermediate_size=cfg.get("intermediate_size", 3072),
        max_position_embeddings=cfg.get("max_position_embeddings", 77),
        hidden_act=cfg.get("hidden_act", "quick_gelu"),
        eos_token_id=cfg.get("eos_token_id", 49407),
        projection_dim=cfg.get("projection_dim") if with_projection else None,
    )


def tiny_clip_config(hidden: int = 32) -> CLIPTextConfig:
    return CLIPTextConfig(
        vocab_size=1000,
        hidden_size=hidden,
        num_hidden_layers=2,
        num_attention_heads=4,
        intermediate_size=64,
        projection_dim=hidden,
    )


def _act(name: str):
    if name == "quick_gelu":
        return lambda x: x * jax.nn.sigmoid(1.702 * x)
    if name in ("gelu", "gelu_new"):
        return lambda x: jax.nn.gelu(x, approximate=False)
    raise ValueError(f"unknown activation {name!r}")


def _self_attn(p, x, heads: int, mask):
    b, l, c = x.shape
    d = c // heads
    scale = d**-0.5
    q = linear(p["q_proj"], x) * scale
    k = linear(p["k_proj"], x)
    v = linear(p["v_proj"], x)
    q = q.reshape(b, l, heads, d)
    k = k.reshape(b, l, heads, d)
    v = v.reshape(b, l, heads, d)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) + mask
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, l, c)
    return linear(p["out_proj"], out)


@jax.named_scope("text_encoder")
def clip_text_forward(params, cfg: CLIPTextConfig, input_ids) -> Dict[str, Any]:
    """Returns {"hidden_states": [L+1 arrays], "last_hidden_state",
    "pooler_output", "text_embeds" (if projection_dim)}.

    ``hidden_states[i]`` is the input to layer i (transformers convention), so
    SDXL's penultimate state is ``hidden_states[-2]``.
    """
    ids = jnp.asarray(input_ids)
    b, l = ids.shape
    x = params["token_embedding"][ids] + params["position_embedding"][None, :l]
    mask = jnp.triu(jnp.full((l, l), -jnp.inf, jnp.float32), k=1)[None, None]

    hidden_states: List[Any] = [x]
    act = _act(cfg.hidden_act)
    for lp in params["layers"]:
        h = _self_attn(lp["self_attn"], layer_norm(lp["layer_norm1"], x), cfg.num_attention_heads, mask)
        x = x + h
        h = linear(lp["mlp"]["fc2"], act(linear(lp["mlp"]["fc1"], layer_norm(lp["layer_norm2"], x))))
        x = x + h
        hidden_states.append(x)

    last = layer_norm(params["final_layer_norm"], x)
    # EOS pooling, matching transformers CLIPTextModel exactly: configs with
    # the legacy eos_token_id == 2 (every published SD/SDXL text_encoder
    # config.json carries it) pool at argmax(ids) — valid because the real
    # EOS token 49407 is the highest id in the CLIP vocab — while modern
    # configs pool at the first position equal to eos_token_id.
    if cfg.eos_token_id == 2:
        eos_pos = jnp.argmax(ids, axis=1)
    else:
        eos_pos = jnp.argmax((ids == cfg.eos_token_id).astype(jnp.int32), axis=1)
    pooled = last[jnp.arange(b), eos_pos]
    out = {
        "hidden_states": hidden_states,
        "last_hidden_state": last,
        "pooler_output": pooled,
    }
    if "text_projection" in params:
        out["text_embeds"] = pooled @ params["text_projection"]["kernel"]
    return out


def init_clip_params(key, cfg: CLIPTextConfig, dtype=jnp.float32):
    ks = jax.random.split(key, cfg.num_hidden_layers + 3)
    d, m = cfg.hidden_size, cfg.intermediate_size

    def lin(k, cin, cout):
        return {
            "kernel": jax.random.normal(k, (cin, cout), jnp.float32) / cin**0.5,
            "bias": jnp.zeros((cout,), jnp.float32),
        }

    def norm():
        return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}

    layers = []
    for i in range(cfg.num_hidden_layers):
        k1, k2, k3, k4, k5, k6 = jax.random.split(ks[i], 6)
        layers.append(
            {
                "layer_norm1": norm(),
                "self_attn": {
                    "q_proj": lin(k1, d, d),
                    "k_proj": lin(k2, d, d),
                    "v_proj": lin(k3, d, d),
                    "out_proj": lin(k4, d, d),
                },
                "layer_norm2": norm(),
                "mlp": {"fc1": lin(k5, d, m), "fc2": lin(k6, m, d)},
            }
        )
    params = {
        "token_embedding": jax.random.normal(ks[-3], (cfg.vocab_size, d), jnp.float32) * 0.02,
        "position_embedding": jax.random.normal(ks[-2], (cfg.max_position_embeddings, d), jnp.float32) * 0.01,
        "layers": layers,
        "final_layer_norm": norm(),
    }
    if cfg.projection_dim:
        params["text_projection"] = {
            "kernel": jax.random.normal(ks[-1], (d, cfg.projection_dim), jnp.float32) / d**0.5
        }
    return jax.tree.map(lambda a: a.astype(dtype), params)
