"""Plain float32 building blocks shared by the family references.

Nothing here imports `distrifuser_tpu`.  Every function follows the published
layer equations (diffusers 0.24 / transformers module semantics) over the
parameter tree the benchmark made from `--seed`; weights arrive in the dtype
they are served in (bf16 on the chip) and are upcast leaf by leaf at the
point of use, so a float32 copy of a whole model never sits beside the served
one.  Callers wrap the jitted pieces in
`jax.default_matmul_precision("highest")`: on a TPU a float32 matmul otherwise
runs in one bf16 pass.
"""

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def f32(x):
    return jnp.asarray(x).astype(F32)


def dense(p, x):
    """x @ kernel (+ bias); kernel is [in, out], upcast at the point of use."""
    y = f32(x) @ f32(p["kernel"])
    return y + f32(p["bias"]) if "bias" in p else y


def conv(p, x, stride=1, pad=None):
    """NHWC conv, HWIO kernel, symmetric (k-1)//2 padding by default."""
    k = f32(p["kernel"])
    kh, kw = k.shape[:2]
    if pad is None:
        pad = (((kh - 1) // 2,) * 2, ((kw - 1) // 2,) * 2)
    y = jax.lax.conv_general_dilated(
        f32(x), k, (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + f32(p["bias"]) if "bias" in p else y


def scan_layers(layer, x, layers):
    """Apply `layer(x, params) -> x` over a list of identically shaped
    parameter trees with one traced body (`lax.scan` over the stacked list):
    the same arithmetic as a Python loop, a fraction of the program size.
    Returns (final x, [x after each layer])."""
    if not layers:
        return x, []
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *layers)

    def body(h, p):
        h = layer(h, p)
        return h, h

    x, every = jax.lax.scan(body, x, stacked)
    return x, [every[i] for i in range(len(layers))]


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return jax.nn.gelu(x, approximate=False)


def layer_norm(p, x, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + eps)
    if p is None:
        return y
    return y * f32(p["scale"]) + f32(p["bias"])


def group_norm(p, x, groups, eps):
    """torch.nn.GroupNorm over NHWC: biased variance per (sample, group)."""
    b, h, w, c = x.shape
    xg = x.reshape(b, h * w, groups, c // groups)
    mean = xg.mean(axis=(1, 3), keepdims=True)
    var = jnp.square(xg - mean).mean(axis=(1, 3), keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + eps)).reshape(b, h, w, c)
    return y * f32(p["scale"]) + f32(p["bias"])


def attention(q, k, v, heads, mask=None):
    """softmax(q k^T / sqrt(d) + mask) v over [B, L, heads*d] tensors."""
    b, lq, c = q.shape
    d = c // heads
    q = q.reshape(b, lq, heads, d)
    k = k.reshape(b, -1, heads, d)
    v = v.reshape(b, -1, heads, d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    if mask is not None:
        logits = logits + mask
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, lq, c)


def sincos_embedding(t, dim, flip_sin_to_cos=True, freq_shift=0,
                     max_period=10000.0):
    """diffusers `get_timestep_embedding`."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=F32)
                    / (half - freq_shift))
    arg = f32(t)[:, None] * freqs[None]
    sin, cos = jnp.sin(arg), jnp.cos(arg)
    return jnp.concatenate([cos, sin] if flip_sin_to_cos else [sin, cos], -1)


def hash_tokenize(texts, vocab_size, eos, bos, max_length):
    """The weightless tokenizer the served pipelines fall back to: crc32 of
    each lower-cased word modulo the vocabulary, BOS first, EOS-padded."""
    ids = np.full((len(texts), max_length), eos, np.int64)
    for i, text in enumerate(texts):
        toks = [bos] + [zlib.crc32(w.encode()) % (vocab_size - 2)
                        for w in text.lower().split()][:max_length - 2]
        toks.append(eos)
        ids[i, :len(toks)] = toks
    return ids


def alphas_cumprod(sched):
    """Cumulative alpha products of the discrete training schedule."""
    n, b0, b1 = (sched["num_train_timesteps"], sched["beta_start"],
                 sched["beta_end"])
    if sched["beta_schedule"] == "scaled_linear":
        betas = np.linspace(b0 ** 0.5, b1 ** 0.5, n) ** 2
    elif sched["beta_schedule"] == "linear":
        betas = np.linspace(b0, b1, n)
    else:
        raise ValueError(f"beta_schedule {sched['beta_schedule']!r}")
    return np.cumprod(1.0 - betas)


def leading_timesteps(sched, steps):
    """diffusers "leading" spacing: arange(steps) * ratio, reversed, offset."""
    ratio = sched["num_train_timesteps"] // steps
    return (np.arange(steps) * ratio)[::-1].astype(np.int64) + sched["steps_offset"]


# -- AutoencoderKL decoder (shared by SDXL and PixArt) -----------------------


def _vae_resnet(p, x, groups):
    h = conv(p["conv1"], silu(group_norm(p["norm1"], x, groups, 1e-6)))
    h = conv(p["conv2"], silu(group_norm(p["norm2"], h, groups, 1e-6)))
    if "conv_shortcut" in p:
        x = conv(p["conv_shortcut"], x)
    return x + h


def _vae_attention(p, x, groups):
    b, h, w, c = x.shape
    hs = group_norm(p["group_norm"], x, groups, 1e-6).reshape(b, h * w, c)
    out = attention(dense(p["to_q"], hs), dense(p["to_k"], hs),
                    dense(p["to_v"], hs), heads=1)
    return x + dense(p["to_out"], out).reshape(b, h, w, c)


def vae_decode(params, cfg, latents):
    """Diffusion-space latents [B, h, w, C] -> image [B, 8h, 8w, 3] in [0, 1]
    (divide by the scaling factor, decode, x/2 + 1/2, clip)."""
    groups = cfg["norm_num_groups"]
    p = params["decoder"]
    z = f32(latents) / cfg["scaling_factor"] + (cfg.get("shift_factor") or 0.0)
    x = conv(p["conv_in"], conv(params["post_quant_conv"], z))
    mid = p["mid_block"]
    x = _vae_resnet(mid["resnets"][0], x, groups)
    x = _vae_attention(mid["attentions"][0], x, groups)
    x = _vae_resnet(mid["resnets"][1], x, groups)
    for up in p["up_blocks"]:
        for rp in up["resnets"]:
            x = _vae_resnet(rp, x, groups)
        if "upsamplers" in up:
            x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
            x = conv(up["upsamplers"][0]["conv"], x)
    x = conv(p["conv_out"],
               silu(group_norm(p["conv_norm_out"], x, groups, 1e-6)))
    return jnp.clip(x / 2 + 0.5, 0.0, 1.0)


def request_noise(seed, shape):
    """A request's initial latent: standard normal from its integer seed."""
    return jax.random.normal(jax.random.PRNGKey(int(seed)), shape, F32)
