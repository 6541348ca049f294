"""Plain float32 reference of the latent-attention think-then-rewrite cell:
the DeepSeek-V3-style language model's full forward (Kanana-2-30B-A3B's
published keys), then few-step SDXL from the ids it ended on.

The language model, as its published description has it (and each departure
in the configuration's `assumed`): with h = RMSNorm(x), eps 1e-6, per
position t

    q_t = h_t W_q  ->  32 heads of [q_nope 128 | q_pe 64]
    [c_t | k_pe_t] = h_t W_kva   (512 | 64);   c_t <- RMSNorm_512(c_t)
    rotary embedding (theta 1e6, pairs (2i, 2i+1)) on every head's q_pe and
        on the ONE k_pe_t all heads share
    k_nope_{t,h} = c_t W_UK,h;  v_{t,h} = c_t W_UV,h
    score_{t,s,h} = (q_nope . k_nope + q_pe . k_pe_s) / sqrt(192), causal,
        softmax;  o_{t,h} = sum_s p v_{s,h};  x <- x + concat_h(o) W_o

then h' = RMSNorm(x); layer 0: x <- x + (silu(h' G) * h' U) D; layers >= 1:
s = sigmoid(h' W_r) over ALL experts, the 6 largest of s + b chosen,
w_i = 2.448 s_i / sum of the chosen s, x <- x + sum over the chosen experts
HELD HERE of w_i (silu(h' G_i) * h' U_i) D_i - a DENSE loop, every held
expert over every token, weighted by the router's weight or zero - plus the
two shared experts as one 1536-wide gated MLP.  Final RMSNorm, head.

Here the per-head keys (192 wide) and values (128 wide) are MATERIALISED for
every position: no cache, no absorption of W_UK / W_UV into the query and the
output, no kernels, no state carried between calls; the queries go in blocks
only so that [heads, queries, keys] fits.  It is given the same share of the
model as the program - the experts held, the slice of the vocabulary - and
the same parameter tree: ``kv_b_proj`` arrives as its per-head halves
``k_up`` [H, 128, 512] (W_UK,h transposed) and ``v_up`` [H, 512, 128], gate
and up-projection as one fused kernel [gate | up]; the same parameters.  It
imports nothing of `distrifuser_tpu`.

What decides `correct` is `reference/nemotron_h_sdxl.py`'s comparison, used
as it is: ONE teacher-forced forward over prompt + served ids, and in the
expert layers over the served choice of experts (held to the reference's own
scores by `lm_router_slack_worst`), against the served logits of every
decoded position; then the image from the served ids.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import _common as C
from ._common import F32, f32, silu
from .nemotron_h_sdxl import Reference as RewriteReference
from .nemotron_h_sdxl import load_limits, logit_readings, prompt_ids
from .unet_sdxl import clip_text, unet

QUERY_BLOCK = 512  # queries a block of the reference's attention


# -- the language model -------------------------------------------------------


def lm_shape(config):
    """The sizes the reference needs, from the configuration's keys."""
    ep = config.get("expert_parallel", {"chips": 1, "index": 0})
    held = config["n_routed_experts"]
    return {
        "layers": config["num_hidden_layers"],
        "dense": config["first_k_dense_replace"],
        "eps": config["rms_norm_eps"], "theta": config["rope_theta"],
        "heads": config["num_attention_heads"],
        "latent": config["kv_lora_rank"], "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"],
        "first_expert": held * ep["index"], "held": held,
        "top_k": config["num_experts_per_tok"],
        "scale": config["routed_scaling_factor"],
    }


def rms_norm(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * f32(scale)


def rotary(x, theta):
    """x [T, ..., R] at positions 0 .. T - 1: the pair (x[2i], x[2i+1])
    turned by position * theta^(-2i / R)."""
    t, r = x.shape[0], x.shape[-1]
    angle = jnp.arange(t, dtype=F32)[:, None] * theta ** (
        -jnp.arange(0, r, 2, dtype=F32) / r)
    angle = angle.reshape((t,) + (1,) * (x.ndim - 2) + (r // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        odd * jnp.cos(angle) + even * jnp.sin(angle)], axis=-1)
    return turned.reshape(x.shape)


def latent_attention(p, s, x):
    """x [T, d] -> [T, d], per-head keys and values materialised."""
    t, h = x.shape[0], s["heads"]
    q = (x @ f32(p["q"]["kernel"])).reshape(t, h, s["nope"] + s["rope"])
    q_nope, q_pe = q[..., :s["nope"]], rotary(q[..., s["nope"]:], s["theta"])
    kv = x @ f32(p["kv_a"]["kernel"])
    c = rms_norm(p["kv_norm"]["scale"], kv[:, :s["latent"]], s["eps"])
    k_pe = rotary(kv[:, s["latent"]:], s["theta"])  # [T, 64], every head's
    k_nope = jnp.einsum("sc,hdc->shd", c, f32(p["k_up"]))
    v = jnp.einsum("sc,hcd->shd", c, f32(p["v_up"]))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], (t, h, s["rope"]))], axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(t, lo + QUERY_BLOCK)
        logits = jnp.einsum("thd,shd->hts", q[lo:hi], k[:hi]) / np.sqrt(
            s["nope"] + s["rope"])
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        w = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", w, v[:hi]))
    return jnp.concatenate(out).reshape(t, -1) @ f32(p["o_proj"]["kernel"])


def gated_mlp(p, x):
    gate, up = jnp.split(x @ f32(p["gate_up"]["kernel"]), 2, axis=-1)
    return (silu(gate) * up) @ f32(p["down"]["kernel"])


def experts(p, s, u, served=None):
    """Router over all experts; of the chosen, those held here computed by a
    dense loop; the shared experts.  -> (out [T, d], router slack).
    ``served`` [T, top_k]: the experts the program chose - the comparison
    is teacher-forced over them, as `reference/nemotron_h_sdxl.py experts`
    has it: the reference's own float32 scores decide whether the served
    choice was a sound one (the slack), and the layer is then computed over
    the served choice with the reference's scores for weights."""
    scores = jax.nn.sigmoid(u @ f32(p["router"]["kernel"]))
    select = scores + f32(p["e_score_correction_bias"])
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
    weights = s["scale"] * chosen / chosen.sum(-1, keepdims=True)

    def one(total, expert):
        e, w1, w2 = expert
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)  # [T]
        gate, up = jnp.split(u @ f32(w1), 2, axis=-1)
        return total + w_e[:, None] * ((silu(gate) * up) @ f32(w2)), None

    ids = s["first_expert"] + jnp.arange(s["held"])
    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (ids, p["experts"]["w1"], p["experts"]["w2"]))
    return routed + gated_mlp(p["shared"], u), slack


class LanguageModel:
    """The full forward, attention and each kind of feed-forward one jitted
    piece (a layer's float32 temporaries at 8704 positions lie beside the
    served weights: the halves are compiled apart so that they fit)."""

    def __init__(self, config):
        self.shape = s = lm_shape(config)
        self._attn = jax.jit(lambda lp, x: x + latent_attention(
            lp["attn"], s, rms_norm(lp["attn_norm"]["scale"], x, s["eps"])))
        self._mlp = jax.jit(lambda lp, x: x + gated_mlp(
            lp["ffn"], rms_norm(lp["ffn_norm"]["scale"], x, s["eps"])))

        def expert_layer(lp, x, served):
            out, slack = experts(
                lp["ffn"], s, rms_norm(lp["ffn_norm"]["scale"], x, s["eps"]),
                served)
            return x + out, slack

        self._experts = jax.jit(expert_layer)
        self._head = jax.jit(lambda p, x: rms_norm(
            p["final_norm"]["scale"], x, s["eps"]) @ f32(p["head"]["kernel"]))

    def logits(self, params, ids, first=0, served_experts=None):
        """ids [T] -> (the logits after each of the tokens ``first`` onward
        [T - first, V], the worst router slack over the expert layers).
        ``served_experts`` [E layers, T, top_k]: the routing the program
        chose, see `experts`."""
        x = f32(params["embed"][jnp.asarray(ids)])
        slack = 0.0
        for i, lp in enumerate(params["layers"]):
            x = self._attn(lp, x)
            if i < self.shape["dense"]:
                x = self._mlp(lp, x)
                continue
            e = i - self.shape["dense"]
            x, worst = self._experts(
                lp, x, None if served_experts is None
                else jnp.asarray(served_experts[e]))
            slack = max(slack, float(worst))
        return self._head(params, x[first:]), slack


# -- prompt -> image ----------------------------------------------------------


class Reference(RewriteReference):
    """`reference/nemotron_h_sdxl.py Reference` with this language model:
    the word-hash prompt, the printed comparison and the image from the
    served ids are its own."""

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

    def served_rewrite(self, request):
        """The served rewrite of this request, from the family module."""
        from benchmark.families.deepseek_v3_sdxl import latest_served

        want = prompt_ids(self.config, request["prompt"])
        for served in reversed(latest_served()):
            if np.array_equal(served.prompt_ids, want):
                return want, served
        raise LookupError(
            "the program kept no served rewrite whose prompt ids are the "
            "reference's own for this request")

    def compare_logits(self, lm_weights, prompt, served):
        """One teacher-forced forward over prompt + served ids - and, in the
        expert layers, over the served choice of experts, which the decode
        program hands back for EVERY position, a snapshot's too - against
        the served logits -> [(name, value, limit, ok)], and the share of
        positions whose largest logit agrees."""
        new_ids = np.asarray(served.new_ids)
        ids = np.concatenate([prompt, new_ids[:-1]])
        routing = np.asarray(served.experts[1])[:, :len(ids)]
        reference, slack = self.lm.logits(lm_weights, ids,
                                          first=len(prompt) - 1,
                                          served_experts=routing)
        readings, agree, self.position_errors = logit_readings(
            served.logits, reference)
        readings["lm_router_slack_worst"] = slack
        return [(name, value, self.limits[name]["limit"],
                 bool(value <= self.limits[name]["limit"]))
                for name, value in readings.items()], agree
