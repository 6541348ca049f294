"""Plain float32 reference of the convolution-attention think-then-rewrite
cell: the LFM2 language model's full forward (LFM2-24B-A2B's published
keys), then few-step SDXL from the ids it ended on.

The language model, as its published description has it (and each departure
in the configuration's `assumed`): every layer x <- x + Mixer(RMSNorm(x)),
x <- x + FFN(RMSNorm(x)), eps 1e-5, plain scales.  With h the normed input:

a conv layer (``layer_types[l] == "conv"``) -

    [B | C | z] = h W_in        three [T, 2048], in this order
    g = B * z
    c_t = k[0] g_{t-2} + k[1] g_{t-1} + k[2] g_t      per channel, g_{-1} =
        g_{-2} = 0: a plain sum over a ZERO-PADDED sequence - no tail, no
        state carried between calls
    m = (C * c) W_out

an attention layer (``"full_attention"``) - 32 query heads over 8 KV heads
of 64: q, k per-head RMS-normalised (q_norm / k_norm [64]), then the
rotate-half rotary embedding over all 64 (theta 1e6), causal softmax of
q . k / 8, keys and values of every position held as computed - no cache -,
the queries in blocks only so that [heads, queries, keys] fits;

then layers 0 and 1 a gated MLP 11776 wide (rows in blocks, so that it fits
beside the served weights), the others a sigmoid router over ALL experts in
float32, the 4 largest of s + b chosen (b: expert_bias, selection only),
w_i = routed_scaling_factor s_i / (sum of the chosen s + 1e-6), a DENSE loop
over the experts held here - every held expert over every token, weighted
by the router's weight or zero -, no shared expert.  Final RMSNorm, and the
head is the embedding: logits = x_norm E^T.

It is given the same share of the model as the program - the experts held,
the slice of the vocabulary - and the same parameter tree (gate | up of the
MLP and of every expert as one fused kernel [gate | up]; the same
parameters).  It imports nothing of `distrifuser_tpu`.

What decides `correct` is `reference/nemotron_h_sdxl.py`'s comparison, as
`reference/deepseek_v3_sdxl.py` uses it: ONE teacher-forced forward over
prompt + served ids, in the expert layers over the served choice of experts
(held to the reference's own scores by `lm_router_slack_worst`), against the
served logits of every decoded position; then the image from the served ids.

And one reading of this cell's own, `lm_cache_float8_nearness`: the same
forward ONCE MORE with the keys and values that a cache would hold rounded to
float8 (e4m3, the precision below the bfloat16 the configuration states)
for every query that the program answers from a cache - the ids that enter
the snapshot and the decoded ones; the snapshot's own positions attend over
their own keys, as the program's do.  The reading is the served logits'
median distance from the forward as stated over their median distance from
the float8 one.  With n a run's own error and d what a float8 cache adds, a
program whose caches are as stated reads n / sqrt(n^2 + d^2) - 0.72-0.76 at
the timed sizes -, and one whose caches are a precision below lies as far
from the one forward as from the other and reads 1 (0.998-1.003: the
stream's own error, 2%, is a sixth of float8's spacing, so the program's
rounding errors are a fresh draw beside the reference's).  Both distances
are taken on the same weights and the same ids, so the seed's own error
level, which moves the plain readings by a fifth, cancels.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import _common as C
from ._common import F32, f32, silu
from .deepseek_v3_sdxl import Reference as LatentReference
from .deepseek_v3_sdxl import gated_mlp, rms_norm
from .nemotron_h_sdxl import load_limits, position_errors
from .nemotron_h_sdxl import prompt_ids  # noqa: F401
from .unet_sdxl import clip_text, unet

QUERY_BLOCK = 512  # queries a block of the reference's attention
ROW_BLOCK = 1024  # positions a block of the dense layers' 11776-wide MLP
ROUTER_EPS = 1e-6


def lm_shape(config):
    """The sizes the reference needs, from the configuration's keys."""
    ep = config.get("expert_parallel", {"chips": 1, "index": 0})
    held = config["num_experts"]
    return {
        "kinds": list(config["layer_types"][:config["num_hidden_layers"]]),
        "dense": config["num_dense_layers"],
        "eps": config["norm_eps"],
        "theta": config["rope_parameters"]["rope_theta"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "first_expert": held * ep["index"], "held": held,
        "top_k": config["num_experts_per_tok"],
        "scale": config["routed_scaling_factor"],
    }


def short_conv(p, x):
    """x [T, d] (normed) -> [T, d]: both gates round the taps, over the
    sequence zero-padded in front."""
    t = x.shape[0]
    b, c, z = jnp.split(x @ f32(p["in_proj"]["kernel"]), 3, axis=-1)
    taps = f32(p["conv"]["kernel"])
    k = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, b.shape[1]), F32), b * z])
    conv = sum(taps[i] * padded[i:i + t] for i in range(k))
    return (c * conv) @ f32(p["out_proj"]["kernel"])


def rotary(x, theta):
    """x [T, H, D] at positions 0 .. T - 1: the pair (x[i], x[i + D/2])
    turned by position * theta^(-2i / D)."""
    t, half = x.shape[0], x.shape[-1] // 2
    angle = jnp.arange(t, dtype=F32)[:, None] * theta ** (
        -jnp.arange(half, dtype=F32) / half)
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def attention(p, s, x, float8_from=None):
    """x [T, d] -> [T, d]: causal grouped-query attention, nothing cached.
    ``float8_from``: the queries from that position on read keys and values
    rounded to float8 - what a cache a precision below would hand them."""
    t, h, kv = x.shape[0], s["heads"], s["kv_heads"]
    q = (x @ f32(p["q"]["kernel"])).reshape(t, h, -1)
    k = (x @ f32(p["k"]["kernel"])).reshape(t, kv, -1)
    v = (x @ f32(p["v"]["kernel"])).reshape(t, kv, -1)
    q = rotary(rms_norm(p["q_norm"]["scale"], q, s["eps"]), s["theta"])
    k = rotary(rms_norm(p["k_norm"]["scale"], k, s["eps"]), s["theta"])
    # each KV head serves h / kv query heads
    k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
    exact = low = (k, v)
    if float8_from is not None:
        low = tuple(f32(a.astype(jnp.float8_e4m3fn)) for a in (k, v))
    edges = sorted(set(range(0, t, QUERY_BLOCK)) | {t, float8_from or 0})
    out = []
    for lo, hi in zip(edges, edges[1:]):
        k, v = exact if float8_from is None or lo < float8_from else low
        logits = jnp.einsum("thd,shd->hts", q[lo:hi], k[:hi]) / np.sqrt(
            q.shape[-1])
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        w = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", w, v[:hi]))
    return jnp.concatenate(out).reshape(t, -1) @ f32(p["o_proj"]["kernel"])


def experts(p, s, u, served=None):
    """Router over all experts; of the chosen, those held here computed by a
    dense loop.  -> (out [T, d], router slack).  ``served`` [T, top_k]: the
    experts the program chose - the comparison is teacher-forced over them,
    as `reference/deepseek_v3_sdxl.py experts` has it: the reference's own
    float32 scores decide whether the served choice was a sound one (the
    slack), and the layer is then computed over the served choice with the
    reference's scores for weights."""
    scores = jax.nn.sigmoid(u @ f32(p["router"]["kernel"]))
    select = scores + f32(p["expert_bias"])
    kth, idx = jax.lax.top_k(select, s["top_k"])
    slack = jnp.zeros(())
    if served is not None:
        idx = jnp.sort(served, axis=-1)
        valid = jnp.all(idx[:, 1:] > idx[:, :-1]) & (idx.min() >= 0) & (
            idx.max() < scores.shape[-1])
        idx = jnp.clip(idx, 0, scores.shape[-1] - 1)
        lowest = jnp.take_along_axis(select, idx, axis=-1).min(-1)
        slack = jnp.where(valid, jnp.max(kth[:, -1] - lowest), jnp.inf)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = s["scale"] * chosen / (chosen.sum(-1, keepdims=True)
                                     + ROUTER_EPS)

    def one(total, expert):
        e, w1, w2 = expert
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)  # [T]
        gate, up = jnp.split(u @ f32(w1), 2, axis=-1)
        return total + w_e[:, None] * ((silu(gate) * up) @ f32(w2)), None

    ids = s["first_expert"] + jnp.arange(s["held"])
    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (ids, p["experts"]["w1"], p["experts"]["w2"]))
    return routed, slack


class LanguageModel:
    """The full forward, each mixer and each kind of feed-forward one
    jitted piece (a layer's float32 temporaries at 8704 positions lie beside
    the served weights: the pieces are compiled apart, and run one after
    another, so that they fit)."""

    def __init__(self, config):
        self.shape = s = lm_shape(config)

        def normed(lp, x):
            return rms_norm(lp["operator_norm"]["scale"], x, s["eps"])

        self._mixers = {
            "conv": jax.jit(lambda lp, x: x + short_conv(
                lp["mixer"], normed(lp, x))),
            "full_attention": jax.jit(
                lambda lp, x, float8_from=None: x + attention(
                    lp["mixer"], s, normed(lp, x), float8_from),
                static_argnums=2)}

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
        # the head is the embedding
        self._head = jax.jit(lambda p, x: rms_norm(
            p["final_norm"]["scale"], x, s["eps"]) @ f32(p["embed"]).T)

    def logits(self, params, ids, first=0, served_experts=None,
               float8_from=None):
        """ids [T] -> (the logits after each of the tokens ``first`` onward
        [T - first, V], the worst router slack over the expert layers).
        ``served_experts`` [E layers, T, top_k]: the routing the program
        chose, see `experts`.  ``float8_from``: see `attention`."""
        x = f32(params["embed"][jnp.asarray(ids)])
        slack = 0.0
        for i, (lp, kind) in enumerate(zip(params["layers"],
                                           self.shape["kinds"])):
            if kind == "full_attention":
                x = self._mixers[kind](lp, x, float8_from)
            else:
                x = self._mixers[kind](lp, x)
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
    (`families/lfm2_sdxl.py` keeps it where that family does), the
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

    def compare_logits(self, lm_weights, prompt, served):
        """`reference/deepseek_v3_sdxl.py`'s readings, and
        `lm_cache_float8_nearness` (the module's docstring): the forward once
        more with float8 keys and values for the queries past the snapshot."""
        checks, agree = super().compare_logits(lm_weights, prompt, served)
        rw, block = self.config["rewrite"], self.config["prefill_block"]
        snapshot = min(rw["instruction_tokens"], len(prompt) - 1
                       ) // block * block
        new_ids = np.asarray(served.new_ids)
        ids = np.concatenate([prompt, new_ids[:-1]])
        low, _ = self.lm.logits(
            lm_weights, ids, first=len(prompt) - 1,
            served_experts=np.asarray(served.experts[1])[:, :len(ids)],
            float8_from=snapshot)
        value = float(np.median(self.position_errors) / max(np.median(
            position_errors(served.logits, low)), 1e-30))
        name = "lm_cache_float8_nearness"
        limit = self.limits[name]["limit"]
        return checks + [(name, value, limit, bool(value <= limit))], agree
