"""Plain float32 reference of the linear-attention think-then-rewrite cell:
the Kimi-Linear language model's full forward (Kimi-Linear-48B-A3B's
published keys), then few-step SDXL from the ids it ended on.

The language model, as its published description has it (and each departure
in the configuration's `assumed`): every layer x <- x + Mixer(RMSNorm(x)),
x <- x + FFN(RMSNorm(x)), eps 1e-5.  With h the normed input, per position t:

a KDA layer (``linear_attn_config.kda_layers``, 1-indexed), 32 heads of 128
key and 128 value channels -

    q, k, v = silu(conv4(h W_q)), silu(conv4(h W_k)), silu(conv4(h W_v))
        depthwise, causal, 4 taps, no bias; q <- q / |q| / sqrt(128),
        k <- k / |k| a head
    g = -exp(A_log)[head] * softplus(h W_fa W_fb + dt_bias)      [32, 128]
    beta = sigmoid(h W_b)                                        [32]
    S' = Diag(exp g) S;  u = beta (v - S'^T k);  S = S' + k u^T;  o = S^T q
    y = (RMSNorm_128(o) * w_norm * sigmoid(h W_ga W_gb)) W_o

computed TOKEN BY TOKEN, the definition: no chunks, no solve, no state
carried between calls (the heads go a few at a time only so that a prompt's
projections fit beside the served weights: a head's recurrence is its own);

a full layer (``full_attn_layers``) - latent attention with NO position
embedding (``mla_use_nope``): q_t = h_t W_q -> 32 heads of 192;
[c_t | k_pe_t] = h_t W_kva (512 | 64), c_t <- RMSNorm_512(c_t); per head
k = [c W_UK,h | k_pe] (the 64-wide part shared by all heads and left as
projected), v = c W_UV,h; causal softmax of q . k / sqrt(192); keys and
values MATERIALISED for every position, the queries in blocks only so that
[heads, queries, keys] fits;

then the feed-forward of `reference/deepseek_v3_sdxl.py`, whose functions
these are: layer 1 a gated MLP, the others a sigmoid router over ALL
experts, the 8 largest of s + b chosen, w_i = 2.446 s_i / sum of the chosen
s, a DENSE loop over the experts held here, plus the shared expert.  Final
RMSNorm, head.

It is given the same share of the model as the program - the experts held,
the slice of the vocabulary - and the same parameter tree: ``W_q | W_k |
W_v`` arrive as one kernel ``qkv`` (and one convolution kernel), ``W_fa |
W_ga | W_b`` as ``gates_in``, ``kv_b_proj`` as its per-head halves; the same
parameters.  It imports nothing of `distrifuser_tpu`.

What decides `correct` is `reference/nemotron_h_sdxl.py`'s comparison, as
`reference/deepseek_v3_sdxl.py` uses it: ONE teacher-forced forward over
prompt + served ids, in the expert layers over the served choice of experts,
against the served logits of every decoded position; then the image from the
served ids.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import _common as C
from ._common import F32, f32, silu
from .deepseek_v3_sdxl import Reference as LatentReference
from .deepseek_v3_sdxl import experts, gated_mlp, rms_norm
from .nemotron_h_sdxl import load_limits, prompt_ids  # noqa: F401
from .unet_sdxl import clip_text, unet

QUERY_BLOCK = 512  # queries a block of the reference's attention
ROW_BLOCK = 1024  # positions a block of the dense layer's 9216-wide MLP
HEAD_BLOCK = 8  # KDA heads whose recurrence runs together
L2_EPS = 1e-6


def lm_shape(config):
    """The sizes the reference needs, from the configuration's keys."""
    ep = config.get("expert_parallel", {"chips": 1, "index": 0})
    held = config["num_experts"]
    linear = config["linear_attn_config"]
    return {
        "kinds": ["kda" if i in linear["kda_layers"] else "mla"
                  for i in range(1, config["num_hidden_layers"] + 1)],
        "dense": config["first_k_dense_replace"],
        "eps": config["rms_norm_eps"],
        "kda_heads": linear["num_heads"], "kda_dim": linear["head_dim"],
        "heads": config["num_attention_heads"],
        "latent": config["kv_lora_rank"], "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"],
        "first_expert": held * ep["index"], "held": held,
        "top_k": config["num_experts_per_token"],
        "scale": config["routed_scaling_factor"],
    }


def l2_normalised(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + L2_EPS)


def kda_heads(p, s, x, first, n):
    """x [T, d] (normed) -> the recurrence's read-out o [T, n, 128] of heads
    ``first .. first + n - 1``, token by token from a zero state."""
    t, h, dk = x.shape[0], s["kda_heads"], s["kda_dim"]

    def of_heads(kernel, groups):
        """[rows, groups * H * dk] -> these heads' columns [rows, groups,
        n, dk]."""
        w = kernel.reshape(kernel.shape[0], groups, h, dk)
        return f32(jax.lax.dynamic_slice_in_dim(w, first, n, axis=2))

    qkv = jnp.einsum("td,dghk->tghk", x, of_heads(p["qkv"]["kernel"], 3))
    taps = of_heads(p["conv"]["kernel"], 3)
    k_taps = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((k_taps - 1,) + qkv.shape[1:], F32),
                              qkv])
    q, k, v = jnp.moveaxis(silu(sum(
        padded[i:i + t] * taps[i] for i in range(k_taps))), 1, 0)
    q, k = l2_normalised(q) / math.sqrt(dk), l2_normalised(k)
    low = x @ f32(p["gates_in"]["kernel"])
    f = jnp.einsum("tr,rghk->tghk", low[:, :dk],
                   of_heads(p["f_b"]["kernel"], 1))[:, 0]
    dt_bias = of_heads(p["dt_bias"][None], 1)[0, 0]
    rate = jax.lax.dynamic_slice_in_dim(f32(p["A_log"]), first, n)
    g = -jnp.exp(rate)[:, None] * jax.nn.softplus(f + dt_bias)  # [T, n, dk]
    beta = jax.nn.sigmoid(jax.lax.dynamic_slice_in_dim(
        low[:, 2 * dk:], first, n, axis=1))  # [T, n]

    def token(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs
        decayed = jnp.exp(g_t)[:, :, None] * state
        u = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", decayed, k_t))
        state = decayed + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((n, dk, dk), F32),
                        (q, k, v, g, beta))
    return o


def kda_output(p, s, x, o):
    """The read-outs of all heads o [T, H, 128] -> the mixer's output: the
    gated norm and the output projection."""
    t, dk = x.shape[0], s["kda_dim"]
    low = x @ f32(p["gates_in"]["kernel"])
    gate = (low[:, dk:2 * dk] @ f32(p["g_b"]["kernel"])).reshape(o.shape)
    o = rms_norm(p["o_norm"]["scale"], o, s["eps"]) * jax.nn.sigmoid(gate)
    return o.reshape(t, -1) @ f32(p["o_proj"]["kernel"])


def latent_attention(p, s, x):
    """x [T, d] -> [T, d], per-head keys and values materialised, nothing
    rotated."""
    t, h = x.shape[0], s["heads"]
    q = (x @ f32(p["q"]["kernel"])).reshape(t, h, s["nope"] + s["rope"])
    kv = x @ f32(p["kv_a"]["kernel"])
    c = rms_norm(p["kv_norm"]["scale"], kv[:, :s["latent"]], s["eps"])
    k_pe = kv[:, s["latent"]:]  # [T, 64], every head's
    k_nope = jnp.einsum("sc,hdc->shd", c, f32(p["k_up"]))
    v = jnp.einsum("sc,hcd->shd", c, f32(p["v_up"]))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], (t, h, s["rope"]))], axis=-1)
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(t, lo + QUERY_BLOCK)
        logits = jnp.einsum("thd,shd->hts", q[lo:hi], k[:hi]) / np.sqrt(
            s["nope"] + s["rope"])
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        w = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", w, v[:hi]))
    return jnp.concatenate(out).reshape(t, -1) @ f32(p["o_proj"]["kernel"])


class LanguageModel:
    """The full forward, each mixer and each kind of feed-forward one
    jitted piece (a layer's float32 temporaries at 8704 positions lie beside
    the served weights: the pieces are compiled apart, and run one after
    another, so that they fit)."""

    def __init__(self, config):
        self.shape = s = lm_shape(config)

        def normed(lp, x):
            return rms_norm(lp["attn_norm"]["scale"], x, s["eps"])

        self._kda_heads = jax.jit(
            lambda lp, x, first, n: kda_heads(lp["attn"], s, normed(lp, x),
                                              first, n), static_argnums=3)
        self._kda_output = jax.jit(lambda lp, x, o: x + kda_output(
            lp["attn"], s, normed(lp, x), o))
        self._attn = jax.jit(lambda lp, x: x + latent_attention(
            lp["attn"], s, normed(lp, x)))

        def mlp(lp, x):
            u = rms_norm(lp["ffn_norm"]["scale"], x, s["eps"])
            return x + jnp.concatenate([
                gated_mlp(lp["ffn"], u[lo:lo + ROW_BLOCK])
                for lo in range(0, x.shape[0], ROW_BLOCK)])

        def expert_layer(lp, x, served):
            out, slack = experts(
                lp["ffn"], s, rms_norm(lp["ffn_norm"]["scale"], x, s["eps"]),
                served)
            return x + out, slack

        self._mlp = jax.jit(mlp)
        self._experts = jax.jit(expert_layer)
        self._head = jax.jit(lambda p, x: rms_norm(
            p["final_norm"]["scale"], x, s["eps"]) @ f32(p["head"]["kernel"]))

    def mixer(self, lp, kind, x):
        if kind == "mla":
            return self._attn(lp, x)
        h = self.shape["kda_heads"]
        n = math.gcd(h, HEAD_BLOCK)
        o = jnp.concatenate([self._kda_heads(lp, x, first, n)
                             for first in range(0, h, n)], axis=1)
        return self._kda_output(lp, x, o)

    def logits(self, params, ids, first=0, served_experts=None):
        """ids [T] -> (the logits after each of the tokens ``first`` onward
        [T - first, V], the worst router slack over the expert layers).
        ``served_experts`` [E layers, T, top_k]: the routing the program
        chose, see `reference/deepseek_v3_sdxl.py experts`."""
        x = f32(params["embed"][jnp.asarray(ids)])
        slack = 0.0
        for i, (lp, kind) in enumerate(zip(params["layers"],
                                           self.shape["kinds"])):
            x = self.mixer(lp, kind, x)
            if i < self.shape["dense"]:
                x = self._mlp(lp, x)
                continue
            e = i - self.shape["dense"]
            x, worst = self._experts(
                lp, x, None if served_experts is None
                else jnp.asarray(served_experts[e]))
            slack = max(slack, float(worst))
        return self._head(params, x[first:]), slack


class Reference(LatentReference):
    """`reference/deepseek_v3_sdxl.py Reference` with this language model:
    the word-hash prompt, the served record it looks the request up in
    (`families/kimi_linear_sdxl.py` keeps it where that family does), the
    comparison over the served ids and the served choice of experts
    (`compare_logits`), the printed line and the image from the served ids
    are its own."""

    def __init__(self, config, height, width):
        self.config, self.h, self.w = config, height, width
        self.lm = LanguageModel(config)
        self.limits = load_limits(config)
        self._clip = [
            jax.jit(lambda p, ids, c=c: clip_text(p, c, ids))
            for c in (config["text_encoder"], config["text_encoder_2"])]
        self._unet = jax.jit(
            lambda p, x, t, enc, te, tid: unet(p, config["unet"], x, t,
                                               enc, te, tid))
        self._decode = jax.jit(lambda p, z: C.vae_decode(p, config["vae"], z))
