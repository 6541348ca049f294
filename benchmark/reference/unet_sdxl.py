"""Plain float32 reference of SDXL text-to-image, prompt -> image.

Two CLIP text towers (penultimate hidden states concatenated, the second
tower's projected EOS embedding as the pooled vector), the
UNet2DConditionModel with text_time conditioning, classifier-free guidance,
deterministic DDIM (eta 0, leading spacing) and the AutoencoderKL decoder -
as diffusers 0.24 / transformers define them.  It reads the benchmark's
configuration dict (the published config.json keys) and the parameter tree
the benchmark made from the seed; it imports nothing of `distrifuser_tpu`.

Departures from the published pipeline, both the served system's too: the
tokenizer is the weightless word hash (no vocabulary ships with the repo), and
the negative branch reuses the positive micro-conditioning ids (diffusers'
behaviour when no negative sizes are passed).
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import _common as C
from ._common import f32, silu


# -- CLIP text tower ----------------------------------------------------------


def clip_text(p, cfg, ids):
    """-> (hidden states list incl. embeddings, projected pooled or None)."""
    heads = cfg["num_attention_heads"]
    act = ((lambda x: x * jax.nn.sigmoid(1.702 * x))
           if cfg["hidden_act"] == "quick_gelu" else C.gelu)
    n = ids.shape[1]
    x = f32(p["token_embedding"])[ids] + f32(p["position_embedding"])[None, :n]
    causal = jnp.triu(jnp.full((n, n), -jnp.inf, C.F32), k=1)[None, None]

    def layer(x, lp):
        h = C.layer_norm(lp["layer_norm1"], x)
        a = lp["self_attn"]
        h = C.attention(C.dense(a["q_proj"], h), C.dense(a["k_proj"], h),
                        C.dense(a["v_proj"], h), heads, causal)
        x = x + C.dense(a["out_proj"], h)
        h = C.layer_norm(lp["layer_norm2"], x)
        return x + C.dense(lp["mlp"]["fc2"], act(C.dense(lp["mlp"]["fc1"], h)))

    embedded = x
    x, after = C.scan_layers(layer, x, p["layers"])
    hidden = [embedded] + after
    pooled = None
    if "text_projection" in p:
        last = C.layer_norm(p["final_layer_norm"], x)
        # transformers' CLIP pooling: the published configs say eos id 2, for
        # which it takes the position of the largest id (the tokenizer's EOS)
        eos = (jnp.argmax(ids, axis=1) if cfg["eos_token_id"] == 2 else
               jnp.argmax((ids == cfg["eos_token_id"]).astype(jnp.int32), 1))
        pooled = C.dense(p["text_projection"],
                         last[jnp.arange(ids.shape[0]), eos])
    return hidden, pooled


# -- UNet2DConditionModel -----------------------------------------------------


def _resnet(p, x, temb, groups):
    h = C.conv(p["conv1"], silu(C.group_norm(p["norm1"], x, groups, 1e-5)))
    h = h + C.dense(p["time_emb_proj"], silu(temb))[:, None, None, :]
    h = C.conv(p["conv2"], silu(C.group_norm(p["norm2"], h, groups, 1e-5)))
    if "conv_shortcut" in p:
        x = C.conv(p["conv_shortcut"], x)
    return x + h


def _attn(p, x, ctx, heads):
    """diffusers Attention with K and V projected by one fused [in, 2C]
    kernel (the tree's `to_kv`: K is the first half of the columns)."""
    k, v = jnp.split(C.dense(p["to_kv"], ctx), 2, axis=-1)
    return C.dense(p["to_out"], C.attention(C.dense(p["to_q"], x), k, v, heads))


def _transformer(p, x, enc, heads, groups):
    b, h, w, c = x.shape
    hs = C.group_norm(p["norm"], x, groups, 1e-6).reshape(b, h * w, c)
    hs = C.dense(p["proj_in"], hs)

    def block(hs, bp):
        n1 = C.layer_norm(bp["norm1"], hs)
        hs = hs + _attn(bp["attn1"], n1, n1, heads)
        hs = hs + _attn(bp["attn2"], C.layer_norm(bp["norm2"], hs), enc,
                        heads)
        g = C.dense(bp["ff"]["net_0"]["proj"], C.layer_norm(bp["norm3"], hs))
        a, gate = jnp.split(g, 2, axis=-1)
        return hs + C.dense(bp["ff"]["net_2"], a * C.gelu(gate))

    hs, _ = C.scan_layers(block, hs, p["transformer_blocks"])
    return C.dense(p["proj_out"], hs).reshape(b, h, w, c) + x


def unet(p, cfg, sample, t, enc, text_embeds, time_ids):
    """Noise prediction for [B, h, w, 4] latents at integer timestep t."""
    if not cfg.get("use_linear_projection", False):
        raise NotImplementedError("reference covers SDXL's linear projections")
    ch = cfg["block_out_channels"]
    groups = cfg["norm_num_groups"]
    heads = cfg["attention_head_dim"]  # diffusers' name for heads per block
    b = sample.shape[0]
    sc = dict(flip_sin_to_cos=cfg["flip_sin_to_cos"],
              freq_shift=cfg["freq_shift"])
    te = p["time_embedding"]
    temb = C.sincos_embedding(jnp.full((b,), t), ch[0], **sc)
    temb = C.dense(te["linear_2"], silu(C.dense(te["linear_1"], temb)))
    ids = C.sincos_embedding(f32(time_ids).reshape(-1),
                             cfg["addition_time_embed_dim"], **sc)
    add = jnp.concatenate([f32(text_embeds), ids.reshape(b, -1)], axis=-1)
    ae = p["add_embedding"]
    temb = temb + C.dense(ae["linear_2"], silu(C.dense(ae["linear_1"], add)))

    x = C.conv(p["conv_in"], f32(sample))
    skips = [x]
    n_down = len(cfg["down_block_types"])
    for i, btype in enumerate(cfg["down_block_types"]):
        bp = p["down_blocks"][i]
        for j in range(cfg["layers_per_block"]):
            x = _resnet(bp["resnets"][j], x, temb, groups)
            if btype == "CrossAttnDownBlock2D":
                x = _transformer(bp["attentions"][j], x, enc, heads[i],
                                 groups)
            skips.append(x)
        if i < n_down - 1:
            x = C.conv(bp["downsamplers"][0]["conv"], x, stride=2)
            skips.append(x)

    mp = p["mid_block"]
    x = _resnet(mp["resnets"][0], x, temb, groups)
    x = _transformer(mp["attentions"][0], x, enc, heads[-1], groups)
    x = _resnet(mp["resnets"][1], x, temb, groups)

    for i, btype in enumerate(cfg["up_block_types"]):
        bp = p["up_blocks"][i]
        for j in range(cfg["layers_per_block"] + 1):
            x = jnp.concatenate([x, skips.pop()], axis=-1)
            x = _resnet(bp["resnets"][j], x, temb, groups)
            if btype == "CrossAttnUpBlock2D":
                x = _transformer(bp["attentions"][j], x, enc,
                                 heads[n_down - 1 - i], groups)
        if i < n_down - 1:
            x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
            x = C.conv(bp["upsamplers"][0]["conv"], x)
    x = silu(C.group_norm(p["conv_norm_out"], x, groups, 1e-5))
    return C.conv(p["conv_out"], x)


# -- prompt -> image ----------------------------------------------------------


def ddim_tables(sched, steps):
    """(timesteps, alpha_t, alpha_prev) of eta-0 DDIM, set_alpha_to_one off."""
    ac = C.alphas_cumprod(sched)
    ts = C.leading_timesteps(sched, steps)
    prev = ts - sched["num_train_timesteps"] // steps
    a_prev = np.where(prev >= 0, ac[np.clip(prev, 0, None)], ac[0])
    return ts, ac[ts].astype(np.float32), a_prev.astype(np.float32)


class Reference:
    """The jitted pieces, built once per (config, size)."""

    def __init__(self, config, height, width):
        self.config, self.h, self.w = config, height, width
        self._clip = [
            jax.jit(lambda p, ids, c=c: clip_text(p, c, ids))
            for c in (config["text_encoder"], config["text_encoder_2"])]
        self._unet = jax.jit(
            lambda p, x, t, enc, te, tid: unet(p, config["unet"], x, t,
                                               enc, te, tid))
        self._decode = jax.jit(
            lambda p, z: C.vae_decode(p, config["vae"], z))

    def encode(self, weights, prompt, negative):
        """([2, 77, 2048] hidden, [2, 1280] pooled), negative branch first."""
        out = []
        for fn, p, c in zip(self._clip, weights["text"],
                            (self.config["text_encoder"],
                             self.config["text_encoder_2"])):
            tok = self.config["tokenizer"]
            ids = C.hash_tokenize([negative, prompt], c["vocab_size"],
                                  tok["eos_token_id"], tok["bos_token_id"],
                                  tok["model_max_length"])
            out.append(fn(p, ids))
        (h1, _), (h2, pooled) = out
        return jnp.concatenate([h1[-2], h2[-2]], axis=-1), pooled

    def generate(self, weights, request):
        """One request -> float32 image [H, W, 3] in [0, 1]."""
        with jax.default_matmul_precision("highest"):
            return self._generate(weights, request)

    def _generate(self, weights, request):
        steps, gs = request["steps"], request["guidance_scale"]
        enc, pooled = self.encode(weights, request["prompt"],
                                  request.get("negative_prompt", ""))
        size = [self.h, self.w, 0, 0, self.h, self.w]
        time_ids = jnp.asarray([size, size], C.F32)
        cin = self.config["unet"]["in_channels"]
        x = C.request_noise(request["seed"], (self.h // 8, self.w // 8, cin))[None]
        ts, a_t, a_prev = ddim_tables(self.config["scheduler"], steps)
        for i in range(steps):
            out = self._unet(weights["unet"], jnp.concatenate([x, x]),
                             int(ts[i]), enc, pooled, time_ids)
            eps = out[:1] + gs * (out[1:] - out[:1])
            x0 = (x - np.sqrt(1.0 - a_t[i]) * eps) / np.sqrt(a_t[i])
            x = np.sqrt(a_prev[i]) * x0 + np.sqrt(1.0 - a_prev[i]) * eps
        return np.asarray(self._decode(weights["vae"], x)[0], np.float32)
