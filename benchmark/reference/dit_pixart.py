"""Plain float32 reference of PixArt-alpha text-to-image, prompt -> image.

T5 v1.1 encoder (RMSNorm, unscaled attention with the shared bucketed
relative-position bias, gated-GELU feed-forward), the PixArt transformer
(patch embedding with 2-D sin-cos positions, adaLN-single modulation, masked
caption cross-attention, the 1024-class resolution / aspect micro-conditioning),
classifier-free guidance, DPM-Solver++ 2M (multistep, lower-order final step)
and the AutoencoderKL decoder - as arXiv 2310.00426 and the diffusers /
transformers modules define them.  It reads the benchmark's configuration
dict and the parameter tree the benchmark made from the seed, and imports
nothing of `distrifuser_tpu`.

Departures from the published pipeline, all the served system's too: the
tokenizer is the weightless word hash; the transformer predicts epsilon only
(the learned-sigma half of the published 8-channel head is unused at
inference); blocks are stored stacked on a leading [depth] axis.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import _common as C
from ._common import f32, silu


def gelu_tanh(x):
    return jax.nn.gelu(x, approximate=True)


# -- T5 encoder ---------------------------------------------------------------


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(scale)


def _position_buckets(cfg, length):
    """Bidirectional T5 bucketing of (key - query) offsets."""
    half = cfg["relative_attention_num_buckets"] // 2
    max_dist = cfg["relative_attention_max_distance"]
    pos = np.arange(length)
    rel = pos[None, :] - pos[:, None]
    bucket = np.where(rel > 0, half, 0)
    rel = np.abs(rel)
    exact = half // 2
    large = exact + (np.log(np.maximum(rel, 1) / exact)
                     / math.log(max_dist / exact) * (half - exact)).astype(np.int64)
    return bucket + np.where(rel < exact, rel, np.minimum(large, half - 1))


def t5_encode(p, cfg, ids, mask):
    """[B, L] ids, [B, L] mask (1 = attended) -> [B, L, d_model]."""
    b, n = ids.shape
    heads, dk = cfg["num_heads"], cfg["d_kv"]
    eps = cfg["layer_norm_epsilon"]
    x = f32(p["shared"])[ids]
    bias = f32(p["relative_attention_bias"])[_position_buckets(cfg, n)]
    bias = bias.transpose(2, 0, 1)[None]  # [1, heads, Lq, Lk]
    bias = bias + jnp.where(mask[:, None, None, :] > 0, 0.0, -1e9)

    def layer(x, lp):  # the tree stacks the layers on a leading axis
        h = _rms_norm(x, lp["attn_norm"], eps)
        a = lp["attn"]
        q, k, v = (C.dense(a[n_], h).reshape(b, n, heads, dk) for n_ in "qkv")
        w = jax.nn.softmax(jnp.einsum("bqhd,bkhd->bhqk", q, k) + bias, -1)
        att = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, n, heads * dk)
        x = x + C.dense(a["o"], att)
        h = _rms_norm(x, lp["ff_norm"], eps)
        ff = lp["ff"]
        x = x + C.dense(ff["wo"], gelu_tanh(C.dense(ff["wi_0"], h))
                        * C.dense(ff["wi_1"], h))
        return x, None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    return _rms_norm(x, p["final_norm"], eps)


# -- PixArt transformer -------------------------------------------------------


def _freq_features(t, dim):
    """DiT timestep features: cos then sin of t * 10000^(-i/half)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=C.F32) / half)
    arg = jnp.asarray(t, C.F32) * freqs
    return jnp.concatenate([jnp.cos(arg), jnp.sin(arg)], -1)


def _pos_table(hidden, side, base, interpolation_scale):
    """diffusers PatchEmbed 2-D sin-cos table [side*side, hidden]: the first
    half of the channels encodes the column, the second half the row."""
    dim = hidden // 2
    omega = 1.0 / (10000.0 ** (np.arange(dim // 2, dtype=np.float64)
                               / (dim // 2)))
    coords = np.arange(side, dtype=np.float64) / (side / base) / interpolation_scale
    ax = coords[:, None] * omega[None]
    ax = np.concatenate([np.sin(ax), np.cos(ax)], -1)
    rows, cols = np.repeat(ax, side, axis=0), np.tile(ax, (side, 1))
    return jnp.asarray(np.concatenate([cols, rows], -1), C.F32)


def _mlp2(p, x, act, a="fc1", b="fc2"):
    return C.dense(p[b], act(C.dense(p[a], x)))


def pixart(p, cfg, sample, t, cap, cap_mask, height, width):
    """Epsilon for [B, h, w, 4] latents; cap [B, Lt, caption] from T5."""
    ps = cfg["patch_size"]
    heads = cfg["num_attention_heads"]
    hidden = heads * cfg["attention_head_dim"]
    b, hh, ww, c = sample.shape
    gh, gw = hh // ps, ww // ps
    tok = f32(sample).reshape(b, gh, ps, gw, ps, c).transpose(0, 1, 3, 2, 4, 5)
    tok = tok.reshape(b, gh * gw, ps * ps * c)
    scale = cfg.get("interpolation_scale") or max(cfg["sample_size"] // 64, 1)
    x = C.dense(p["proj_in"], tok) + _pos_table(
        hidden, gh, cfg["sample_size"] // ps, float(scale))[None]

    fdim = 256
    temb = _mlp2({"fc1": p["t_fc1"], "fc2": p["t_fc2"]},
                 _freq_features(t, fdim), silu)
    if cfg.get("use_additional_conditions", cfg["sample_size"] == 128):
        def embed(ep, vals):
            f = jnp.stack([_freq_features(v, fdim) for v in vals])
            return _mlp2(ep, f, silu).reshape(-1)
        temb = temb + jnp.concatenate([
            embed(p["resolution_embedder"], (float(height), float(width))),
            embed(p["aspect_ratio_embedder"], (float(height) / float(width),))])
    c6 = C.dense(p["adaln"], silu(temb)).reshape(6, hidden)

    y = _mlp2({"fc1": p["cap_fc1"], "fc2": p["cap_fc2"]}, f32(cap),
              gelu_tanh)
    cap_bias = jnp.where(cap_mask[:, None, None, :] > 0, 0.0, -1e9)

    def block(x, bp):  # the tree stacks the blocks on a leading axis
        mods = f32(bp["scale_shift_table"]) + c6  # [6, hidden]
        s1, sc1, g1, s2, sc2, g2 = (mods[j][None, None] for j in range(6))
        hn = C.layer_norm(None, x, 1e-6) * (1 + sc1) + s1
        k, v = jnp.split(C.dense(bp["attn_kv"], hn), 2, -1)
        att = C.attention(C.dense(bp["attn_q"], hn), k, v, heads)
        x = x + g1 * C.dense(bp["attn_out"], att)
        ck, cv = jnp.split(C.dense(bp["cross_kv"], y), 2, -1)
        catt = C.attention(C.dense(bp["cross_q"], x), ck, cv, heads, cap_bias)
        x = x + C.dense(bp["cross_out"], catt)
        hn = C.layer_norm(None, x, 1e-6) * (1 + sc2) + s2
        x = x + g2 * _mlp2(bp, hn, gelu_tanh, "mlp_fc1", "mlp_fc2")
        return x, None

    x, _ = jax.lax.scan(block, x, p["blocks"])

    mods = f32(p["final_table"]) + temb[None]
    out = C.dense(p["final_out"],
                  C.layer_norm(None, x, 1e-6) * (1 + mods[1]) + mods[0])
    cout = out.shape[-1] // (ps * ps)
    out = out.reshape(b, gh, gw, ps, ps, cout).transpose(0, 1, 3, 2, 4, 5)
    return out.reshape(b, gh * ps, gw * ps, cout)


# -- prompt -> image ----------------------------------------------------------


def dpm_tables(sched, steps):
    """(timesteps, alpha, sigma, lambda) with the sigma -> 0 tail appended."""
    ac = C.alphas_cumprod(sched)
    ts = C.leading_timesteps(sched, steps)
    alpha, sigma = np.sqrt(ac[ts]), np.sqrt(1.0 - ac[ts])
    lam = np.log(alpha) - np.log(sigma)
    return (ts, np.append(alpha, 1.0), np.append(sigma, 0.0),
            np.append(lam, np.inf))


class Reference:
    """The jitted pieces, built once per (config, size)."""

    def __init__(self, config, height, width):
        self.config, self.h, self.w = config, height, width
        self._t5 = jax.jit(
            lambda p, ids, mask: t5_encode(p, config["text_encoder"], ids,
                                           mask))
        self._dit = jax.jit(
            lambda p, x, t, cap, mask: pixart(p, config["transformer"], x,
                                              t, cap, mask, height, width))
        self._decode = jax.jit(
            lambda p, z: C.vae_decode(p, config["vae"], z))

    def encode(self, weights, prompt, negative):
        tok = self.config["tokenizer"]
        ids = C.hash_tokenize([negative, prompt],
                              self.config["text_encoder"]["vocab_size"],
                              tok["eos_token_id"], tok["bos_token_id"],
                              tok["model_max_length"])
        # real tokens and the first EOS are attended; the EOS padding is not
        mask = (ids != tok["eos_token_id"]).astype(np.float32)
        mask[np.arange(len(ids)), np.argmax(ids == tok["eos_token_id"], 1)] = 1.0
        return self._t5(weights["t5"], ids, mask), jnp.asarray(mask)

    def generate(self, weights, request):
        """One request -> float32 image [H, W, 3] in [0, 1]."""
        with jax.default_matmul_precision("highest"):
            return self._generate(weights, request)

    def _generate(self, weights, request):
        steps, gs = request["steps"], request["guidance_scale"]
        cap, mask = self.encode(weights, request["prompt"],
                                request.get("negative_prompt", ""))
        cin = self.config["transformer"]["in_channels"]
        x = C.request_noise(request["seed"], (self.h // 8, self.w // 8, cin))[None]
        ts, alpha, sigma, lam = dpm_tables(self.config["scheduler"], steps)
        x0_prev = None
        for i in range(steps):
            out = self._dit(weights["dit"], jnp.concatenate([x, x]),
                            float(ts[i]), cap, mask)
            eps = out[:1] + gs * (out[1:] - out[:1])
            x0 = (x - sigma[i] * eps) / alpha[i]
            h = lam[i + 1] - lam[i]
            d = x0
            if x0_prev is not None and i < steps - 1:
                r = (lam[i] - lam[i - 1]) / h
                d = (1 + 1 / (2 * r)) * x0 - (1 / (2 * r)) * x0_prev
            x = (sigma[i + 1] / sigma[i]) * x - alpha[i + 1] * np.expm1(-h) * d
            x0_prev = x0
        return np.asarray(self._decode(weights["vae"], x)[0], np.float32)
